"""Share of the traced window's device idle time that falls outside every
``engine.wait`` span: idle the host caused by working (admitting,
launching, retiring) rather than by waiting on a result.  In percent;
``by_span`` gives the idle seconds by the innermost ``engine.*`` span, or
``outside engine``."""

from bench import scopes as S


def read(record):
    events = (record.get("trace") or {}).get("events")
    by = S.idle_by_span(events) if events else {}
    total = sum(by.values())
    if not total or set(by) == {S.OUTSIDE}:
        return None
    return {"value": 100.0 * (total - by.get("engine.wait", 0.0)) / total,
            "by_span": by}
