"""Host time per engine step that is not spent blocked on a device result,
in ms: the ``engine.step`` spans that lie whole in the traced window, less
the ``engine.wait`` spans inside them, over their number (the span form of
``(step_s - wait_s) / steps`` of ContinuousServingEngine)."""

from bench import scopes as S


def read(record):
    events = (record.get("trace") or {}).get("events")
    steps, step_s, wait_s = S.host_step_seconds(events) if events else (0,) * 3
    if not steps:
        return None
    return {"value": (step_s - wait_s) / steps * 1e3, "steps": steps}
