"""Profiler traces: capture, and the reduction from a trace to device
time.

The reduction works on a compact event list, one dict per event:
``{"plane", "line", "name", "t" (start, ns), "d" (duration, ns), "meta"}``.
``load_events`` makes that list from the ``.xplane.pb`` that
``jax.profiler`` writes; tests feed a recorded list directly.

How a TPU v5e trace names things (jax 0.9, read from a trace taken on one v5e chip):
device planes are ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one
event per executable run, ``jit_<fn>(<hash>)``), ``XLA Ops`` (one event per
HLO operation, named by its whole HLO text, ``%<op> = <shape> <opcode>(...)``)
and ``Async XLA Ops``.  A Pallas kernel is a ``custom-call`` whose op is
named after the kernel function (``%fused_swiglu_pallas.2 = ...``); fusions
are ``%fusion.N``, ``%<kind>_fusion``.  Host planes (``/host:CPU``) hold a
line per thread; the Python thread carries the benchmark's own
``bench.*`` annotations and, with the Python tracer on, ``$file:line fn``
events.  Host and device events share one clock.

Here an ``XLA Ops`` event's ``name`` is the op's own name (``%`` and the
HLO text after `` = `` dropped) and ``meta`` the start of that HLO text, so
a reader matches a kernel by name without matching the ops that merely take
its output as an operand.  Busy time is the union of the op intervals;
the traced window is the span of the benchmark's own host spans.
"""

from __future__ import annotations

import collections
import gzip
import json
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def start(trace_dir: Path) -> None:
    """Start the profiler; an earlier run's trace there is removed first."""
    import shutil
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    Path(trace_dir).mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(trace_dir))


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def newest_xplane(trace_dir: Path) -> Path | None:
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def load_events(path: Path) -> list[dict]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        dev = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            for e in line.events:
                name, meta = e.name, ""
                if dev and " = " in name:
                    name, meta = name.split(" = ", 1)
                    name, meta = name.lstrip("%"), meta[:200]
                out.append({"plane": plane.name, "line": line.name,
                            "name": name, "t": int(e.start_ns),
                            "d": int(e.duration_ns), "meta": meta})
    return out


def save_events(events: list[dict], path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read_saved(path: Path) -> list[dict]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def device_ops(events: list[dict]) -> dict[str, list[dict]]:
    """Operation events by device plane."""
    by = collections.defaultdict(list)
    for e in events:
        if e["line"] == OPS_LINE and DEVICE_PLANE.match(e["plane"]):
            by[e["plane"]].append(e)
    return dict(by)


def union(intervals) -> list[tuple[int, int]]:
    """Merge (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def covered(intervals) -> int:
    return sum(b - a for a, b in union(intervals))


def window_ns(events: list[dict]) -> tuple[int, int]:
    """The traced window: the span of the benchmark's own host spans
    (``bench.*``) where the trace has them, else of every event."""
    own = [e for e in events if e["name"].startswith("bench.")
           and not DEVICE_PLANE.match(e["plane"])]
    src = own or events
    return (min(e["t"] for e in src), max(e["t"] + e["d"] for e in src))


def matches(e: dict, pattern: re.Pattern) -> bool:
    return bool(pattern.search(e["name"]) or pattern.search(e["meta"]))


def op_seconds(events: list[dict], pattern: str) -> float | None:
    """Summed device time of the operations matching ``pattern`` (searched
    in the op's name, then in its HLO text), averaged over the chips; None
    if none ran."""
    pat = re.compile(pattern)
    ops = device_ops(events)
    tot, hit = 0, False
    for evs in ops.values():
        for e in evs:
            if matches(e, pat):
                tot += e["d"]
                hit = True
    return tot / 1e9 / max(1, len(ops)) if hit else None


def _host_labels(host: list[dict], times) -> list[str]:
    """For each time, the innermost host span (latest start) covering it."""
    import numpy as np
    if not host:
        return ["no host span"] * len(times)
    st = np.array([e["t"] for e in host], np.int64)
    en = st + np.array([e["d"] for e in host], np.int64)
    out = []
    for t in times:
        cov = np.nonzero((st <= t) & (en > t))[0]
        out.append(host[cov[np.argmax(st[cov])]]["name"] if len(cov)
                   else "no host span")
    return out


def self_times(evs: list[dict]):
    """(name, self ns) per op: its duration less the ops nested in it (a
    ``while`` of a layer scan holds its body's ops on the same line)."""
    out = []
    stack = []                       # [end, index] of the open ancestors
    order = sorted(range(len(evs)),
                   key=lambda i: (evs[i]["t"], -evs[i]["d"]))
    self_ns = [e["d"] for e in evs]
    for i in order:
        e = evs[i]
        while stack and stack[-1][0] <= e["t"]:
            stack.pop()
        end = e["t"] + e["d"]
        if stack and end <= stack[-1][0]:
            self_ns[stack[-1][1]] -= e["d"]
        stack.append((end, i))
    for e, ns in zip(evs, self_ns):
        out.append((e["name"], max(0, ns)))
    return out


def reduce(events: list[dict]) -> dict:
    """Busy and window seconds (mean over chips), the device operations
    with the most self time, and the longest idle gaps by what the host was
    doing."""
    ops = device_ops(events)
    if not ops:
        return {}
    t0, t1 = window_ns(events)
    busy = [covered((max(e["t"], t0), min(e["t"] + e["d"], t1))
                    for e in evs if e["t"] < t1 and e["t"] + e["d"] > t0)
            for evs in ops.values()]
    per_op = collections.Counter()
    for evs in ops.values():
        for name, ns in self_times(evs):
            per_op[name] += ns
    n = len(ops)
    top = [[k, v / 1e9 / n] for k, v in per_op.most_common(10)]
    first = sorted(ops)[0]
    merged = union((e["t"], e["t"] + e["d"]) for e in ops[first])
    gaps = []
    prev = t0
    for a, b in merged + [(t1, t1)]:
        a, b = min(max(a, t0), t1), min(max(b, t0), t1)
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    host = [e for e in events if e["plane"].startswith("/host")]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:200]
    by_label = collections.Counter()
    for (a, b), lab in zip(longest,
                           _host_labels(host, [(a + b) // 2
                                               for a, b in longest])):
        by_label[lab] += b - a
    idle = [[k, v / 1e9] for k, v in by_label.most_common(10)]
    return {"busy_s": sum(busy) / n / 1e9, "window_s": (t1 - t0) / 1e9,
            "chips": n,
            "breakdown": {"device_ops": top, "idle_gaps": idle}}


def reduce_dir(trace_dir: Path) -> dict:
    """Load the newest trace under ``trace_dir`` and reduce it; the events
    stay in the result for the metric readers."""
    path = newest_xplane(trace_dir)
    if path is None:
        return {}
    events = load_events(path)
    out = reduce(events)
    out["events"] = events
    return out
