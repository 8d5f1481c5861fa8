"""Device time of one pool decode: the mean duration of the runs of the
``jit_serve_decode`` executable (its ``XLA Modules`` events) that start in
the traced window, in ms."""

from bench import scopes as S


def read(record):
    events = (record.get("trace") or {}).get("events")
    runs = S.module_seconds(events, "jit_serve_decode") if events else []
    if not runs:
        return None
    return {"value": 1e3 * sum(runs) / len(runs), "runs": len(runs)}
