"""Runtime calibration of the pipe cost constants (paper §3.2 hardware model).

Every cost model in the repo — :func:`pipesim.plan_slices` slicing the
fused_pipe shuffles, :class:`commplan.LinkCosts` scoring flat-vs-hier comm
paths, the attention-stream bubble estimate — runs off three constants on
:class:`dcomm.DcommConfig`:

    pipe_stage_bw    descriptor-interpreting staging copy (HBM-class)
    pipe_wire_bw     cross-device link (NIC / ICI-class)
    pipe_overhead_s  per-slice setup (descriptor fetch + dispatch)

The defaults are the paper's A100/CX-7 numbers.  On any other platform they
mis-rank the knee (slice counts, flat/hier crossover), so :func:`calibrate`
measures all three on the *running* platform with tiny timed probes and
:func:`apply` threads them into a ``DcommConfig`` via ``dataclasses.replace``
— downstream consumers (``pipe_geometry`` -> ``PipeParams``,
``LinkCosts.from_dcomm``) pick them up with no further changes.

Probes (min-of-repeats, post-compile, ``block_until_ready``):

    stage_bw    a jitted row-gather over a ~4 MiB buffer — the same memory
                pattern as the Pallas staging kernels (read + write counted)
    wire_bw     a timed ``device_put`` of the buffer to another device when
                one exists (host-platform CPU "devices" give a copy-bandwidth
                proxy).  With one device there is no wire to time: the table
                carries stage_bw / 4, an assumption that keeps the
                wire-slower-than-staging ordering the simulator needs, and
                marks it ``wire_measured=False``
    overhead_s  a jitted scalar op — pure dispatch latency

Measured rates are clamped to sane positive-finite bounds: a calibration
that produced 0, inf, or nan would silently wedge the discrete-event
simulator, so we refuse to emit one.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

_MIN_BW = 1e6           # 1 MB/s — below this the timer, not the copy, is wrong
_MAX_BW = 1e16
_MIN_OVH = 1e-9
_MAX_OVH = 1e-1


@dataclasses.dataclass(frozen=True)
class CalibrationTable:
    """Measured pipe constants for the running platform.

    The serialized form (``as_dict``) is the calibration-table format
    documented in DESIGN.md §kernels: three floats plus provenance.
    """
    stage_bw: float          # bytes/s
    wire_bw: float           # bytes/s
    overhead_s: float        # seconds per dispatch
    platform: str = "unknown"
    payload_bytes: int = 0
    wire_measured: bool = True   # False: wire_bw is assumed, not timed

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def _clamp(x: float, lo: float, hi: float) -> float:
    if not (x == x) or x <= 0:      # nan or nonpositive -> floor
        return lo
    return min(max(x, lo), hi)


def _timeit(fn, repeats: int) -> float:
    """Best-of-N wall time of fn(); fn must block on completion itself."""
    fn()                             # compile / warm caches
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return max(best, 1e-9)


def calibrate(payload_bytes: int = 1 << 22,
              repeats: int = 5) -> CalibrationTable:
    """Measure stage/wire/overhead on the current default backend."""
    n = max(1, payload_bytes // 4)               # f32 rows of width 1
    d = 128
    rows = max(1, n // d)
    x = jnp.ones((rows, d), jnp.float32)
    idx = jnp.arange(rows, dtype=jnp.int32)[::-1]
    actual_bytes = rows * d * 4

    gather = jax.jit(lambda a, i: jnp.take(a, i, axis=0))
    t_stage = _timeit(lambda: gather(x, idx).block_until_ready(), repeats)
    stage_bw = 2.0 * actual_bytes / t_stage      # read + write

    devices = jax.devices()
    wire_measured = len(devices) > 1
    if wire_measured:
        src = jax.device_put(x, devices[0])
        t_wire = _timeit(
            lambda: jax.device_put(src, devices[1]).block_until_ready(),
            repeats)
        wire_bw = actual_bytes / t_wire
    else:
        wire_bw = stage_bw / 4.0                 # assumed: wire < stage

    tiny = jnp.zeros((8,), jnp.float32)
    reduce = jax.jit(jnp.sum)
    overhead = _timeit(lambda: reduce(tiny).block_until_ready(), repeats)

    return CalibrationTable(
        stage_bw=_clamp(stage_bw, _MIN_BW, _MAX_BW),
        wire_bw=_clamp(wire_bw, _MIN_BW, _MAX_BW),
        overhead_s=_clamp(overhead, _MIN_OVH, _MAX_OVH),
        platform=jax.default_backend(),
        payload_bytes=actual_bytes,
        wire_measured=wire_measured,
    )


def apply(table: CalibrationTable, cfg):
    """Return ``cfg`` (a DcommConfig) with the measured pipe constants.

    Everything downstream reads the constants off the config —
    ``dcomm.pipe_geometry`` builds ``pipesim.PipeParams`` from them and
    ``commplan.LinkCosts.from_dcomm`` maps stage->intra / wire->inter — so
    this replace is the whole integration.
    """
    return dataclasses.replace(cfg,
                               pipe_stage_bw=table.stage_bw,
                               pipe_wire_bw=table.wire_bw,
                               pipe_overhead_s=table.overhead_s)
