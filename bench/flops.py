"""Operations and bytes of a decoder-only MoE model, counted from its
published shapes and the work a run routed: every matmul is 2 operations
per multiply-add, experts count only the rows routed to them (tokens x
top_k), and bytes are bf16 weights read once per call plus activations in
and out.  Remat, capacity padding and masked rows are not counted.

``arch`` is a configuration file's ``model`` block.
"""

from __future__ import annotations

BF16 = 2


def _dims(a):
    d, hd = a["hidden_size"], a["head_dim"]
    return (d, hd, a["num_attention_heads"], a["num_key_value_heads"],
            a["num_experts"], a["num_experts_per_tok"],
            a["moe_intermediate_size"], a["vocab_size"],
            a["num_hidden_layers"])


def layer_linear_flops(a) -> float:
    """Per token and layer: attention projections, router, routed experts."""
    d, hd, h, kv, e, k, f, v, L = _dims(a)
    proj = 2 * d * (h * hd + 2 * kv * hd) + 2 * h * hd * d
    return proj + 2 * d * e + k * 3 * 2 * d * f


def attention_flops(a, ctx: float) -> float:
    """Per token and layer: scores and weighted values over ``ctx`` keys."""
    d, hd, h, kv, e, k, f, v, L = _dims(a)
    return 4 * h * hd * ctx


def head_flops(a) -> float:
    return 2 * a["hidden_size"] * a["vocab_size"]


def prefill_flops(a, n: int) -> float:
    """A prompt of n tokens: every layer, causal attention, one head row."""
    L = a["num_hidden_layers"]
    quad = attention_flops(a, 1.0) * n * (n + 1) / 2
    return L * (n * layer_linear_flops(a) + quad) + head_flops(a)


def decode_flops(a, ctx: int) -> float:
    """One output token whose query sees ``ctx`` cached positions."""
    L = a["num_hidden_layers"]
    return L * (layer_linear_flops(a) + attention_flops(a, ctx)) + \
        head_flops(a)


def experts_hit(a, rows: int) -> float:
    """Expected number of a layer's experts that receive at least one of
    ``rows`` tokens under uniform top-k routing."""
    e, k = a["num_experts"], a["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def expert_ffn_work(a, tokens: int) -> tuple[float, float]:
    """(operations, bytes) of one layer's expert FFN over ``tokens`` tokens:
    tokens x top_k routed rows through SwiGLU; the weights of the experts
    that received rows; routed rows in and out."""
    d, f, k = a["hidden_size"], a["moe_intermediate_size"], \
        a["num_experts_per_tok"]
    rows = tokens * k
    ops = rows * 3 * 2 * d * f
    by = experts_hit(a, tokens) * 3 * d * f * BF16 + 2 * rows * d * BF16
    return float(ops), float(by)


def least_time(ops: float, by: float, peak_flops: float, peak_bw: float):
    """(seconds, bound): the larger of compute time and memory time."""
    tc, tm = ops / peak_flops, by / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
