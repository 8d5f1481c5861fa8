"""The program's own names in a profiler trace: the executable each device
op ran in, the named scope (``jax.named_scope``) it was traced under, and
the serving engine's ``engine.*`` host spans.  The per-layer metrics that
read them call this module on the compact event list of ``bench/trace.py``.

A device's ``XLA Modules`` line holds one event per executable run, named
``jit_<fn>(<program id>)``, and the ops of the run lie inside it on the
same chip.  An op event carries no ``op_name``.  That is in the HLO of its
executable, which the trace's ``/host:metadata`` plane holds for every
executable of the process, under the same ``jit_<fn>(<program id>)``
(:func:`op_names`).  So the ops of each run are scoped from the very
executable that ran: each prefill bucket is one.  A program without named
scopes, named executables or ``engine.*`` spans leaves every reading here
empty.
"""

from __future__ import annotations

import collections
from pathlib import Path

from bench import trace as T

MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO = "Hlo Proto"
# where bench/run.py has the profiler write, one directory per cell
TRACES = Path(__file__).resolve().parents[1] / "bench_out" / "trace"

# the program's named scopes (models/lm.py, layers/moe.py, core/fusco.py,
# core/dcomm.py, serving/engine.py)
SCOPES = ("layers", "attention", "moe.route", "moe.dispatch",
          "moe.expert_ffn", "moe.combine", "moe.decode", "lm_head")
SCAN_IO = "layers.scan_io"
UNSCOPED = "unscoped"
ENGINE = "engine."                 # the serving engine's host spans
OUTSIDE = "outside engine"


def scope_of(op_name: str) -> str:
    """The innermost of the program's named scopes in an ``op_name``.
    Work of the layer scan under no finer scope (the per-layer slices of
    the stacked parameters, the write-back of its outputs, the loop itself)
    is ``layers.scan_io``, and an op under none of them ``unscoped``."""
    inner = UNSCOPED
    for part in op_name.split("/"):
        if part in SCOPES:
            inner = part
    return SCAN_IO if inner == "layers" else inner


def module_name(program: str) -> str:
    """``jit_serve_decode(8902...)`` -> ``jit_serve_decode``."""
    return program.split("(", 1)[0]


# ---------------------------------------------------------------------------
# The HLO of the trace's /host:metadata plane, read straight from the
# protobuf wire format.  Field numbers, from tsl's xplane.proto and xla's
# hlo.proto: XSpace.planes 1; XPlane.name 2, .event_metadata 4,
# .stat_metadata 5 (maps, the value in field 2 of an entry);
# XEventMetadata.name 2, .stats 5; XStatMetadata.id 1, .name 2;
# XStat.metadata_id 1, .bytes_value 6; HloProto.hlo_module 1;
# HloModuleProto.computations 3; HloComputationProto.instructions 2;
# HloInstructionProto.name 1, .metadata 7; OpMetadata.op_name 2.

def _varint(b, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """(field number, value) of one message: an int, or a memoryview of
    the bytes of a length-delimited field."""
    b = memoryview(b)
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _get(b, field: int, default=b""):
    for f, v in _fields(b):
        if f == field:
            return v
    return default


def hlo_op_names(hlo_proto) -> dict[str, str]:
    """HLO instruction name -> ``op_name`` of one serialized HloProto."""
    out = {}
    for f, comp in _fields(_get(hlo_proto, 1)):
        if f != 3:
            continue
        for g, ins in _fields(comp):
            if g == 2:
                meta = _get(ins, 7)
                out[bytes(_get(ins, 1)).decode()] = (
                    bytes(_get(meta, 2)).decode() if len(meta) else "")
    return out


def op_names(xspace: bytes) -> dict[str, dict[str, str]]:
    """Program (``jit_<fn>(<program id>)``) -> {HLO instruction name:
    ``op_name``} for every executable whose HLO the serialized XSpace (an
    ``.xplane.pb``) holds."""
    for f, plane in _fields(xspace):
        if f != 1 or bytes(_get(plane, 2)) != METADATA_PLANE.encode():
            continue
        stat_names, programs = {}, []
        for g, entry in _fields(plane):
            if g == 5:
                sm = _get(entry, 2)
                stat_names[_get(sm, 1, 0)] = bytes(_get(sm, 2)).decode()
            elif g == 4:
                programs.append(_get(entry, 2))
        out = {}
        for em in programs:
            for g, stat in _fields(em):
                if g == 5 and stat_names.get(_get(stat, 1, 0)) == HLO_PROTO:
                    out[bytes(_get(em, 2)).decode()] = hlo_op_names(
                        _get(stat, 6))
        return out
    return {}


def find_op_names(events: list[dict],
                  module: str) -> dict[str, dict[str, str]]:
    """The op names (:func:`op_names`) of the programs of ``module`` that
    ran in ``events``, from the newest trace under :data:`TRACES` that
    holds any of them (the run's own: each traced run clears its cell's
    directory); {} where none ran or no trace holds them."""
    need = {e["name"] for e in events if e["line"] == MODULES_LINE
            and module_name(e["name"]) == module}
    if not need:
        return {}
    files = sorted(TRACES.glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime, reverse=True)
    for path in files:
        try:
            names = op_names(path.read_bytes())
        except (ValueError, IndexError, UnicodeDecodeError):
            continue
        held = {k: names[k] for k in need if k in names}
        if held:
            return held
    return {}


# ---------------------------------------------------------------------------
# Reductions of the compact event list.

def runs(events: list[dict]) -> dict[tuple, list[dict]]:
    """The device ops of each executable run, keyed (chip, start,
    program); ops outside every run are left out."""
    import bisect
    spans = collections.defaultdict(list)
    for e in events:
        if e["line"] == MODULES_LINE and T.DEVICE_PLANE.match(e["plane"]):
            spans[e["plane"]].append((e["t"], e["t"] + e["d"], e["name"]))
    for s in spans.values():
        s.sort()
    starts = {p: [a for a, _, _ in s] for p, s in spans.items()}
    out = collections.defaultdict(list)
    for plane, evs in T.device_ops(events).items():
        for e in evs:
            i = bisect.bisect_right(starts.get(plane, []), e["t"]) - 1
            if i >= 0 and e["t"] < spans[plane][i][1]:
                a, _, prog = spans[plane][i]
                out[(plane, a, prog)].append(e)
    return dict(out)


def scope_seconds(events: list[dict], module: str,
                  names: dict[str, dict[str, str]]) -> dict[str, float]:
    """Device self time of the runs of one executable (``module``, e.g.
    ``jit_serve_decode``) by scope, in seconds, mean over the chips, most
    first; only runs of the programs ``names`` holds count, and {} where
    none did."""
    by = collections.Counter()
    for (_, _, prog), evs in runs(events).items():
        table = names.get(prog)
        if module_name(prog) != module or table is None:
            continue
        for e, (_, ns) in zip(evs, T.self_times(evs)):
            by[scope_of(table.get(e["name"], ""))] += ns
    n = max(1, len(T.device_ops(events)))
    return {k: v / 1e9 / n for k, v in by.most_common()}


def scope_share(record: dict, module: str, scopes) -> dict | None:
    """Share of one executable's device self time spent in ``scopes``, in
    percent, with the seconds of every scope as ``by_scope``; None where
    the traced window holds no run of it, or its ops carry no scope."""
    events = (record.get("trace") or {}).get("events")
    if not events:
        return None
    by = scope_seconds(events, module, find_op_names(events, module))
    total = sum(by.values())
    if not total or set(by) == {UNSCOPED}:
        return None
    part = sum(by.get(s, 0.0) for s in scopes)
    return {"value": 100.0 * part / total, "by_scope": by}


def module_seconds(events: list[dict], module: str) -> list[float]:
    """Device duration of each run of one executable (its ``XLA Modules``
    events) that starts in the traced window, on every chip."""
    t0, t1 = T.window_ns(events)
    return [e["d"] / 1e9 for e in events
            if e["line"] == MODULES_LINE and T.DEVICE_PLANE.match(e["plane"])
            and module_name(e["name"]) == module and t0 <= e["t"] < t1]


def idle_gaps(evs: list[dict], t0: int, t1: int) -> list[tuple[int, int]]:
    """The intervals of [t0, t1) in which none of one chip's ops runs."""
    gaps = []
    prev = t0
    for a, b in T.union((e["t"], e["t"] + e["d"]) for e in evs) + [(t1, t1)]:
        a, b = min(max(a, t0), t1), min(max(b, t0), t1)
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    return gaps


def engine_spans(events: list[dict]) -> list[dict]:
    return [e for e in events if e["name"].startswith(ENGINE)
            and not T.DEVICE_PLANE.match(e["plane"])]


def idle_by_span(events: list[dict]) -> dict[str, float]:
    """Device idle time of the traced window in seconds (mean over chips)
    by the innermost ``engine.*`` host span around it (the latest started
    of those covering it), or ``outside engine``; most first."""
    import numpy as np
    ops = T.device_ops(events)
    if not ops:
        return {}
    t0, t1 = T.window_ns(events)
    spans = engine_spans(events)
    st = np.array([e["t"] for e in spans], np.int64)
    en = st + np.array([e["d"] for e in spans], np.int64)
    by = collections.Counter()
    for evs in ops.values():
        for a, b in idle_gaps(evs, t0, t1):
            cov = np.nonzero((st < b) & (en > a))[0]
            cuts = sorted({a, b} | {int(x) for x in st[cov] if a < x < b}
                          | {int(x) for x in en[cov] if a < x < b})
            for lo, hi in zip(cuts, cuts[1:]):
                inside = [i for i in cov if st[i] <= lo and en[i] >= hi]
                lab = (spans[max(inside, key=lambda i: (st[i], -en[i]))]
                       ["name"] if inside else OUTSIDE)
                by[lab] += hi - lo
    return {k: v / 1e9 / len(ops) for k, v in by.most_common()}


def host_step_seconds(events: list[dict]) -> tuple[int, float, float]:
    """(steps, step seconds, wait seconds) of the ``engine.step`` spans
    that lie whole in the traced window, and of the ``engine.wait`` spans
    inside them: the span form of the engine's ``steps``, ``step_s`` and
    ``wait_s`` counters."""
    t0, t1 = T.window_ns(events)
    spans = engine_spans(events)
    steps = [(e["t"], e["t"] + e["d"]) for e in spans
             if e["name"] == "engine.step" and t0 <= e["t"]
             and e["t"] + e["d"] <= t1]
    waits = [(e["t"], e["t"] + e["d"]) for e in spans
             if e["name"] == "engine.wait"]
    wait = sum(b - a for a, b in waits
               if any(s <= a and b <= e for s, e in steps))
    return (len(steps), sum(b - a for a, b in steps) / 1e9, wait / 1e9)
