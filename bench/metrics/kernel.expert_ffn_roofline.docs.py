"""Expert-FFN kernel (``fused_swiglu``, kernels/fused_staging.py) share of
its roofline, in percent: the least time of the routed work the traced
steps asked of it, over the summed device time of its events.

Least time is counted call by call and layer by layer from the routed
work (``bench/flops.expert_ffn_work``: tokens x top_k rows, the weights of
the experts that received rows, rows in and out), never from the kernel's
capacity-padded launch shape, and is the larger of operations over the bf16
peak and bytes over HBM bandwidth.  ``bound`` says which of the two held
for most of that time."""

from bench import flops as F
from bench import trace as T

KERNEL = r"^fused_swiglu_pallas\b"


def read(record):
    tr = record.get("trace") or {}
    calls = record.get("trace_calls")
    if not tr.get("events") or not calls:
        return None
    spent = T.op_seconds(tr["events"], KERNEL)
    if not spent:
        return None
    model, peak = record["model"], record["peak"]
    layers = model["num_hidden_layers"]
    least = {"compute": 0.0, "memory": 0.0}
    for _, tokens in calls:
        ops, by = F.expert_ffn_work(model, tokens)
        t, bound = F.least_time(ops, by, peak["bf16_flops"], peak["hbm_bw"])
        least[bound] += t * layers
    total = sum(least.values())
    return {"value": 100.0 * total / spent,
            "bound": max(least, key=least.get),
            "kernel_s": spent, "least_s": total}
