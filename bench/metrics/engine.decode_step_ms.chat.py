"""The engine's own pool-decode time per step over the window
(``decode_s / decode_steps`` deltas of ContinuousServingEngine)."""


def read(record):
    if not record.get("decode_steps"):
        return None
    return record["decode_s"] / record["decode_steps"] * 1e3
