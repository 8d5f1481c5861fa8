"""Model FLOP/s utilisation of serving: the operations the model requires
for the prompt tokens prefilled and the output tokens emitted in the window
(``bench/flops.py``: routed experts only, attention at each token's
context), per second of window, over the chips' bf16 peak, in percent."""


def read(record):
    f = record.get("model_flops")
    if not f:
        return None
    peak = record["peak"]["bf16_flops"] * record["chips"]
    return 100.0 * f / record["window_s"] / peak
