"""dComm — the Data-Fused Communication Engine (paper §3.2), TPU-native.

Five interchangeable wire engines, all driven by the same planner descriptors:

============  =========  =========  ==========  =====================================
engine        levels     padding    pipelined   notes
============  =========  =========  ==========  =====================================
fused_flat    1          capacity   no          ONE descriptor-driven gather stages
                                                tokens straight into (dest lane ×
                                                local-expert × capacity) sub-slots;
                                                the tiled ``all_to_all`` lands every
                                                token already expert-grouped, the FFN
                                                consumes in place, combine scatter-
                                                adds straight home.  Zero intermediate
                                                permutation passes (the dComm
                                                property).
fused_pipe    1          capacity   **yes**     Same flat plan, but the staging buffer
                                    (+cross-    is split into S slices along the
                                    layer)      capacity axis and streamed: slice i's
                                                grouped FFN + combine overlap slice
                                                i+1's gather + all_to_all (double-
                                                buffered ``lax.scan`` carry — the
                                                paper's producer/consumer ring,
                                                Fig. 5).  S comes from
                                                ``pipesim.plan_slices`` or the
                                                ``pipe_slices`` knob.  The slice
                                                primitives are split into issue/
                                                consume halves; a shuffle can end
                                                with its tail slice still in flight
                                                (``PipeTail``), which is how
                                                ``fusco.pipe_layer_stream`` removes
                                                the per-layer barrier between the
                                                combine of MoE layer i and the
                                                dispatch of layer i+1 (joint slice
                                                count from
                                                ``pipesim.plan_layer_stream``), and
                                                how ``fusco.interleaved_layer_
                                                stream`` round-robins K token
                                                micro-batches through one schedule
                                                holding K tails in flight — lane
                                                j+1's router + grouped FFN is the
                                                tail-independent work that FILLS
                                                lane j's boundary window (count
                                                from ``pipesim.plan_interleaved_
                                                stream``).  ``fusco.tx_layer_
                                                stream`` fills it at K=1 with the
                                                ATTENTION block of a parallel
                                                attention+MoE transformer layer
                                                (count from ``pipesim.plan_tx_
                                                stream``); a pure MoE chain still
                                                leaves the K=1 window empty.
fused_hier    2          capacity   no          Node-level forwarding with dedup (one
                                                copy per token per destination node,
                                                forwarder lane picked by the Online
                                                Load Balancer) + expert-level
                                                distribution from piggybacked
                                                metadata; combine pre-reduces per-node
                                                partials on the forwarder, so the slow
                                                tier carries deduplicated bytes both
                                                directions.
disagg        1          capacity   no          The disaggregated baseline (§2.3):
                                                sort-by-destination pass → all-to-all
                                                → sort-by-expert pass → FFN → inverse,
                                                each sort a materialised permutation.
ragged        1          none       no          ``jax.lax.ragged_all_to_all`` whose
                                                offset/size operands ARE the segment
                                                descriptors, both directions: combine
                                                runs the reverse exchange with the
                                                send/recv roles swapped
                                                (``ragged_reverse_descriptors``) and
                                                scatter-adds straight home.  TPU-only
                                                (XLA:CPU can't compile it);
                                                descriptor construction + inversion
                                                are unit-tested on CPU.
============  =========  =========  ==========  =====================================

All entry points run **inside shard_map** over the expert-parallel axis/axes.

Placement: every engine is placement-agnostic — it only consumes the
placement *interface* (``ep`` / ``node_size`` / ``experts_per_lane`` /
``lane_of_expert`` / ``local_expert_index`` / ``node_of_lane`` /
``replica_count``), so both the arithmetic ``routing.ExpertPlacement`` and
the table-driven ``relayout.TablePlacement`` (arbitrary expert→lane tables
with per-expert replica counts, produced by the load-adaptive re-layout
solver from ``traffic.py`` EMA statistics) drive the same descriptors.
Conformance under arbitrary tables is enforced per engine in
``tests/test_engines.py``.

Overflow: capacity drops used to be silent (``mode="drop"`` scatters); each
dispatch now surfaces the shard's drop count as ``DispatchResult.dropped``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import pipesim
from repro.core import planner as planner_lib
from repro.core.descriptors import drop_neg, gather_rows
from repro.core.routing import ExpertPlacement
from repro.kernels import ops as kops

I32 = jnp.int32


@dataclasses.dataclass(frozen=True)
class DcommConfig:
    """Static configuration of the shuffle engine."""
    engine: str = "fused_hier"            # fused_flat | fused_pipe | fused_hier | disagg | ragged
    ep_axis: Any = "model"                # axis name, or (pod_axis, model_axis)
    node_size: int = 4                    # lanes per (virtual) node; multi-pod: =model size
    capacity_factor: float = 2.0
    use_balancer: bool = True             # Online Load Balancer on/off (§5.4)
    # dispatch-side dedup/condense (commplan): ship ONE wire row per distinct
    # (token, dest lane) pair — duplicates from a token's top-k hitting the
    # same lane (a fortiori the same remote expert) are expanded on the
    # landing side from piggybacked metadata.  Honored when the flat wire is
    # taken (fused_flat); other engines ignore it (fused_hier already dedups
    # at node level), so the flag can ride in a mixed per-layer config.
    dedup: bool = False
    # fused_pipe slice knobs: 0 slices = auto via pipesim.plan_slices at the
    # hardware point below (defaults: TPU v5e HBM staging / ICI wire).
    pipe_slices: int = 0
    pipe_stage_bw: float = 819e9
    pipe_wire_bw: float = 50e9
    pipe_overhead_s: float = 2e-6

    @property
    def model_axis(self) -> str:
        return self.ep_axis[-1] if isinstance(self.ep_axis, (tuple, list)) else self.ep_axis

    @property
    def pod_axis(self) -> str | None:
        return self.ep_axis[0] if isinstance(self.ep_axis, (tuple, list)) else None


def _cap(n_expected: float, factor: float, align: int = 8) -> int:
    c = max(align, int(-(-n_expected * factor // align)) * align)
    return c


def _lane_index(cfg: DcommConfig, placement: ExpertPlacement) -> jax.Array:
    m = jax.lax.axis_index(cfg.model_axis)
    if cfg.pod_axis is not None:
        p = jax.lax.axis_index(cfg.pod_axis)
        return p * (placement.ep // jax.lax.axis_size(cfg.pod_axis)) + m
    return m


def _node_groups(ep: int, node_size: int) -> list[list[int]]:
    return [list(range(n * node_size, (n + 1) * node_size))
            for n in range(ep // node_size)]


class DispatchResult(NamedTuple):
    """What the expert FFN consumes: a landed buffer already grouped by local
    expert, plus everything combine() needs to route outputs home."""
    expert_rows: jax.Array      # (S, E_local, C, d) rows for this lane's experts
    row_gates: jax.Array | None  # (S, E_local, C) gates (hier) or None (flat)
    state: Any                  # engine-private
    # capacity-overflow drop count observed BY this shard (scalar — drops
    # were previously silent mode="drop" scatters): sum(max(0, count -
    # capacity)) over the slot-table groups this shard builds.  For the
    # single-level engines (flat/pipe/ragged) that is purely this shard's
    # own sender-side assignments; hier and disagg also count their
    # forwarder/receiver-stage drops, which concern OTHER shards' tokens —
    # so per-shard attribution is engine-dependent and only the psum over
    # the EP axis is globally meaningful.
    dropped: jax.Array | None = None


def _flat_exchange(buf: jax.Array, cfg: DcommConfig, ep: int,
                   reverse: bool = False) -> jax.Array:
    """Tiled exchange of a lane-major buffer over the EP axis/axes.

    ``buf`` is (EP, rows, ...); the leading axis is the destination lane on
    dispatch and the origin lane on combine (``reverse=True`` runs the
    two-level multi-pod exchange in the opposite order so it inverts the
    forward one).
    """
    if cfg.pod_axis is None:
        return jax.lax.all_to_all(buf, cfg.model_axis, 0, 0, tiled=True)
    npod = jax.lax.axis_size(cfg.pod_axis)
    buf = buf.reshape((npod, ep // npod) + buf.shape[1:])
    if reverse:
        buf = jax.lax.all_to_all(buf, cfg.pod_axis, 0, 0, tiled=True)
        buf = jax.lax.all_to_all(buf, cfg.model_axis, 1, 1, tiled=True)
    else:
        buf = jax.lax.all_to_all(buf, cfg.model_axis, 1, 1, tiled=True)
        buf = jax.lax.all_to_all(buf, cfg.pod_axis, 0, 0, tiled=True)
    return buf.reshape((ep,) + buf.shape[2:])


# ======================================================================
# fused_flat
# ======================================================================

def flat_dispatch(x: jax.Array, A: jax.Array, gates: jax.Array,
                  placement: ExpertPlacement, cfg: DcommConfig) -> DispatchResult:
    t, d = x.shape
    k = A.shape[1]
    e_local = placement.experts_per_lane
    cap = _cap(t * k / (placement.ep * e_local), cfg.capacity_factor)
    plan = planner_lib.build_flat_plan(A, gates, placement, cap)

    # ONE fused gather: original layout -> comm buffer (EP, E_local*C, d).
    # Kernel-routed: the descriptor interpretation IS the Pallas index_map
    # when use_pallas(), so rows stream into slot order without an
    # intermediate materialisation (jnp reference otherwise).
    buf = kops.segment_gather(x, plan.src_of_slot)           # (EP*E_local*C, d)
    buf = _flat_exchange(buf.reshape(placement.ep, e_local * cap, d), cfg,
                         placement.ep)
    # landed layout: (source lane, E_local, C, d) — expert-grouped already.
    expert_rows = buf.reshape(placement.ep, e_local, cap, d)
    return DispatchResult(expert_rows, None, (plan, t, d, cap), plan.dropped)


def flat_combine(expert_out: jax.Array, res: DispatchResult,
                 placement: ExpertPlacement, cfg: DcommConfig) -> jax.Array:
    plan, t, d, cap = res.state
    e_local = placement.experts_per_lane
    buf = _flat_exchange(expert_out.reshape(placement.ep, e_local * cap, d),
                         cfg, placement.ep, reverse=True)
    buf = buf.reshape(placement.ep * e_local * cap, d)
    # fused weighted scatter-add straight into the original token layout
    return kops.segment_scatter_add(buf, plan.src_of_slot,
                                    plan.gate_of_slot, t)


# ======================================================================
# fused_flat + dedup/condense (commplan mechanism b)
# ======================================================================

def dedup_dispatch(x: jax.Array, A: jax.Array, gates: jax.Array,
                   placement: ExpertPlacement,
                   cfg: DcommConfig) -> DispatchResult:
    """Condensed flat dispatch: one wire row per distinct (token, dest lane).

    Same single tiled exchange as ``flat_dispatch`` but over the condensed
    plan — duplicate (source, destination) pairs created by a token's top-k
    landing several experts on one lane (replicated hot experts, small
    node counts) share a row.  The landing lane expands rows per local
    expert from the piggybacked metadata (``build_stage2_plan`` with
    ``node_size=1`` — a purely local gather, no second exchange), so the
    expert FFN sees exactly the grouped layout of the dense path.
    """
    t, d = x.shape
    k = A.shape[1]
    ep = placement.ep
    e_local = placement.experts_per_lane
    # condensed rows per dest lane: distinct lanes per token <= min(k, ep)
    c1 = _cap(t * min(k, ep) / ep, cfg.capacity_factor)
    # expansion rows per local expert: the landing lane receives ~t*k
    # assignments from ALL lanes, spread over its e_local groups (total
    # buffer rows e_local*c2 == the dense flat engine's ep*e_local*cap)
    c2 = _cap(t * k / e_local, cfg.capacity_factor)

    plan1 = planner_lib.build_condensed_plan(A, gates, placement, c1)
    buf = kops.segment_gather(x, plan1.src_of_slot)          # (EP*C1, d)
    buf = _flat_exchange(buf.reshape(ep, c1, d), cfg, ep)
    me = _flat_exchange(plan1.meta_expert.reshape(ep, c1, k), cfg, ep)
    mg = _flat_exchange(plan1.meta_gate.reshape(ep, c1, k), cfg, ep)

    # fan-out expansion, local to the landing lane (node_size=1: keys are
    # this lane's local expert indices directly)
    plan2 = planner_lib.build_stage2_plan(
        me.reshape(ep * c1, k), mg.reshape(ep * c1, k), 1, e_local, c2)
    buf2 = kops.segment_gather(buf.reshape(ep * c1, d), plan2.src_of_slot)
    expert_rows = buf2.reshape(1, e_local, c2, d)
    row_gates = plan2.gate_of_slot.reshape(1, e_local, c2)
    return DispatchResult(expert_rows, row_gates,
                          (plan1, plan2, t, d, c1, c2),
                          plan1.dropped + plan2.slots.dropped())


def dedup_combine(expert_out: jax.Array, res: DispatchResult,
                  placement: ExpertPlacement, cfg: DcommConfig) -> jax.Array:
    """Combine for the condensed path: gate at the expert, pre-reduce the
    lane's per-row partials (the reverse of the fan-out expansion), reverse
    the condensed exchange, scatter-add home.  The wire carries condensed
    bytes both directions — the same property ``fused_hier`` has at node
    level, here at lane level with zero extra hops."""
    plan1, plan2, t, d, c1, c2 = res.state
    ep = placement.ep
    out = expert_out * res.row_gates[..., None].astype(expert_out.dtype)
    out = out.reshape(-1, d)
    # landing-lane pre-combine: sum this lane's expert partials per wire row
    part = kops.segment_scatter_add(
        out, plan2.src_of_slot, jnp.ones(out.shape[:1], jnp.float32), ep * c1)
    part = _flat_exchange(part.reshape(ep, c1, d), cfg, ep, reverse=True)
    # origin: gates were applied at the expert, dedup handled by the
    # landing-lane pre-combine — plain scatter-add per condensed row.
    part = part.reshape(ep * c1, d)
    return kops.segment_scatter_add(
        part, plan1.src_of_slot, jnp.ones((ep * c1,), jnp.float32), t)


# ======================================================================
# fused_pipe — the paper's pipelined engine (Fig. 5) on the flat plan,
# split into issue/consume slice primitives so a schedule (single-shuffle
# or cross-layer stream) can hold slices in flight explicitly.
# ======================================================================

def pipe_geometry(t: int, k: int, d: int, itemsize: int,
                  placement: ExpertPlacement, cfg: DcommConfig,
                  n_layers: int = 1, interleave: int = 1,
                  attn_s: float = 0.0) -> tuple[int, int]:
    """(capacity, n_slices) for a pipelined shuffle — static trace-time plan.

    ``t`` is the tokens of ONE shuffle (one micro-batch lane when the caller
    interleaves).  S is ``cfg.pipe_slices`` when set; else the pipesim knee
    for the staging buffer's byte volume at the config's hardware point: the
    *joint* cross-layer knee from :func:`pipesim.plan_layer_stream` when the
    shuffle is one layer of an ``n_layers`` stream, the interleaved-
    schedule knee from :func:`pipesim.plan_interleaved_stream` (full-layer
    payload = ``interleave`` lanes) when micro-batches are interleaved
    through it, and the attention-filled knee from
    :func:`pipesim.plan_tx_stream` when ``attn_s > 0`` (the caller's estimate
    of per-lane attention compute seconds — the tail-independent window
    filler of the ``moe_tx`` stream).  Clamped so every slice keeps at least
    one row per (lane, expert) sub-slot; capacity is rounded up to a
    multiple of S.
    """
    e_local = placement.experts_per_lane
    cap = _cap(t * k / (placement.ep * e_local), cfg.capacity_factor)
    if cfg.pipe_slices > 0:
        s = cfg.pipe_slices
    else:
        payload = float(placement.ep * e_local * cap * d * itemsize)
        p = pipesim.params_from_dcomm(payload, cfg)
        if attn_s > 0.0:
            s = pipesim.plan_tx_stream(
                p, max(1, n_layers), max(1, interleave), attn_s,
                payload_bytes=payload * max(1, interleave))["n_slices"]
        elif interleave > 1:
            s = pipesim.plan_interleaved_stream(
                p, max(1, n_layers), interleave,
                payload_bytes=payload * interleave)["n_slices"]
        elif n_layers > 1:
            s = pipesim.plan_layer_stream(p, n_layers)["n_slices"]
        else:
            s = pipesim.plan_slices(p)["n_slices"]
    s = max(1, min(int(s), cap))
    cap = int(-(-cap // s)) * s                       # round up to S slices
    return cap, s


@jax.named_scope("moe.dispatch")
def _pipe_slice_plan(x: jax.Array, A: jax.Array, gates: jax.Array,
                     placement: ExpertPlacement, cfg: DcommConfig):
    """Build the flat plan with capacity rounded so it splits into S slices."""
    t, d = x.shape
    cap, s = pipe_geometry(t, A.shape[1], d, x.dtype.itemsize, placement, cfg)
    plan = planner_lib.build_flat_plan(A, gates, placement, cap)
    sliced = planner_lib.slice_flat_plan(plan, placement, cap, s)
    return plan, sliced, cap, s


@jax.named_scope("moe.dispatch")
def pipe_issue(x: jax.Array, src_slice: jax.Array, placement: ExpertPlacement,
               cfg: DcommConfig) -> jax.Array:
    """Producer half of one slice: descriptor gather stages it, the tiled
    exchange puts it on the wire.

    ``src_slice`` is (EP, E_local, Cs); returns the landed (EP(source lane),
    E_local, Cs, d) sub-buffer — the same layout as ``fused_flat``, one
    capacity stripe at a time.
    """
    ep, d = placement.ep, x.shape[1]
    _, e_local, cs = src_slice.shape
    buf = kops.segment_gather(x, src_slice.reshape(-1))
    buf = _flat_exchange(buf.reshape(ep, e_local * cs, d), cfg, ep)
    return buf.reshape(ep, e_local, cs, d)


@jax.named_scope("moe.combine")
def pipe_return_issue(out_slice: jax.Array, placement: ExpertPlacement,
                      cfg: DcommConfig) -> jax.Array:
    """Wire half of one slice's combine: reverse tiled exchange of the expert
    outputs; returns the (EP*E_local*Cs, d) rows back on their origin lane."""
    ep = placement.ep
    e_local, cs, d = out_slice.shape[1:]
    buf = _flat_exchange(out_slice.reshape(ep, e_local * cs, d), cfg, ep,
                         reverse=True)
    return buf.reshape(ep * e_local * cs, d)


@jax.named_scope("moe.combine")
def pipe_return_consume(y: jax.Array, returned: jax.Array,
                        src_slice: jax.Array, gate_slice: jax.Array,
                        t: int) -> jax.Array:
    """Local half of one slice's combine: weighted scatter-add into ``y``."""
    return y + kops.segment_scatter_add(returned, src_slice.reshape(-1),
                                        gate_slice.reshape(-1), t)


def pipe_consume(y: jax.Array, landed: jax.Array, src_slice: jax.Array,
                 gate_slice: jax.Array,
                 ffn: Callable[[jax.Array], jax.Array], t: int,
                 placement: ExpertPlacement, cfg: DcommConfig) -> jax.Array:
    """Consumer half of one slice: grouped FFN + both combine halves.
    ``landed`` is a (EP, E_local, Cs, d) sub-buffer from :func:`pipe_issue`;
    ``ffn`` maps it to expert outputs of the same shape."""
    returned = pipe_return_issue(ffn(landed), placement, cfg)
    return pipe_return_consume(y, returned, src_slice, gate_slice, t)


class PipeTail(NamedTuple):
    """The in-flight queue entry that survives a shuffle's epilogue: one slice
    whose combine *exchange* has been issued but whose scatter-add has not
    landed.  Carrying it across a layer boundary removes the per-layer
    *program* barrier in the cross-layer stream — the boundary becomes one
    async-ready exchange instead of a materialised layer output.  The window
    it opens is filled whenever the schedule co-locates tail-independent work
    there: ``fusco.interleaved_layer_stream`` holds K of these in flight (one
    per token micro-batch lane, stacked on a leading axis in the layer-scan
    carry) and fills lane j's window with lane j+1's router + FFN compute.
    A plain K=1 ``fusco.pipe_layer_stream`` keeps the structure but leaves
    the window empty (a pure MoE chain has no such work of its own).
    """
    returned: jax.Array        # (EP*E_local*Cs, d) reverse-exchanged outputs
    src: jax.Array             # (EP, E_local, Cs) origin token per slot
    gate: jax.Array            # (EP, E_local, Cs) combine weight per slot


def pipe_empty_tail(placement: ExpertPlacement, cs: int, d: int,
                    dtype, gate_dtype) -> PipeTail:
    """A tail whose consumption is a no-op (all slots empty) — the stream's
    initial carry before any layer has a slice in flight."""
    ep, e_local = placement.ep, placement.experts_per_lane
    return PipeTail(jnp.zeros((ep * e_local * cs, d), dtype),
                    jnp.full((ep, e_local, cs), -1, I32),
                    jnp.zeros((ep, e_local, cs), gate_dtype))


def pipe_empty_tails(placement: ExpertPlacement, cs: int, d: int, dtype,
                     gate_dtype, k: int) -> PipeTail:
    """K stacked no-op tails (leading axis = micro-batch lane): the initial
    carry of the interleaved stream, one in-flight queue entry per lane."""
    one = pipe_empty_tail(placement, cs, d, dtype, gate_dtype)
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (k,) + a.shape), one)


def pipe_tail_consume(y: jax.Array, tail: PipeTail, t: int) -> jax.Array:
    """Land a deferred tail slice: the scatter-add that completes ``y``."""
    return pipe_return_consume(y, tail.returned, tail.src, tail.gate, t)


def pipe_shuffle_ffn_stream(x: jax.Array, A: jax.Array, gates: jax.Array,
                            ffn: Callable[[jax.Array], jax.Array],
                            placement: ExpertPlacement, cfg: DcommConfig,
                            y0: jax.Array | None = None
                            ) -> tuple[jax.Array, PipeTail]:
    """One shuffle of the cross-layer stream: pipelined like
    :func:`pipe_shuffle_ffn`, but the tail slice's scatter-add is NOT taken —
    its combine exchange is issued and handed back as a :class:`PipeTail` for
    the caller to land later (typically in the next layer's prologue, after
    which the next router runs).  ``y0`` seeds the accumulator (the residual
    stream input), so the returned partial output is ``y0 + all but the tail
    slice's contribution``.
    """
    t, d = x.shape
    _, sliced, _, s = _pipe_slice_plan(x, A, gates, placement, cfg)

    def consume(y, landed, src_slice, gate_slice):
        return pipe_consume(y, landed, src_slice, gate_slice, ffn, t,
                            placement, cfg)

    y = jnp.zeros((t, d), x.dtype) if y0 is None else y0
    landed = pipe_issue(x, sliced.src[0], placement, cfg)    # prologue: slice 0
    if s > 1:
        def body(carry, xs):
            y, landed = carry
            src_next, src_cur, gate_cur = xs
            landed_next = pipe_issue(x, src_next, placement, cfg)
            y = consume(y, landed, src_cur, gate_cur)        # overlaps the wire
            return (y, landed_next), None
        (y, landed), _ = jax.lax.scan(
            body, (y, landed),
            (sliced.src[1:], sliced.src[:-1], sliced.gate[:-1]))
    # tail: FFN + combine exchange issued; the scatter-add is deferred.
    out = ffn(landed)
    returned = pipe_return_issue(out, placement, cfg)
    return y, PipeTail(returned, sliced.src[-1], sliced.gate[-1])


def pipe_shuffle_ffn(x: jax.Array, A: jax.Array, gates: jax.Array,
                     ffn: Callable[[jax.Array], jax.Array],
                     placement: ExpertPlacement, cfg: DcommConfig) -> jax.Array:
    """The fully fused pipelined path: slice i's FFN + combine overlap slice
    i+1's gather + all_to_all.

    The double-buffered carry holds (accumulated output, landed slice i);
    each scan step first *issues* slice i+1's communication, then consumes
    slice i — XLA's async collectives (TPU) overlap the in-flight exchange
    with the grouped FFN, exactly the producer/consumer ring of Fig. 5.
    ``ffn`` maps a landed (EP, E_local, Cs, d) sub-buffer to expert outputs of
    the same shape.
    """
    y, tail = pipe_shuffle_ffn_stream(x, A, gates, ffn, placement, cfg)
    return pipe_tail_consume(y, tail, x.shape[0])


def pipe_dispatch(x: jax.Array, A: jax.Array, gates: jax.Array,
                  placement: ExpertPlacement, cfg: DcommConfig) -> DispatchResult:
    """Split-phase API: pipelined comm only, landed buffer identical to
    ``fused_flat`` (the FFN-overlapped path is :func:`pipe_shuffle_ffn`)."""
    t, d = x.shape
    e_local = placement.experts_per_lane
    plan, sliced, cap, s = _pipe_slice_plan(x, A, gates, placement, cfg)
    landed = jax.lax.map(
        lambda src: pipe_issue(x, src, placement, cfg), sliced.src)
    # (S, EP, E_local, Cs, d) -> (EP, E_local, C, d): slices are capacity stripes
    expert_rows = landed.transpose(1, 2, 0, 3, 4).reshape(
        placement.ep, e_local, cap, d)
    return DispatchResult(expert_rows, None, (sliced, t, d, cap, s),
                          plan.dropped)


def pipe_combine(expert_out: jax.Array, res: DispatchResult,
                 placement: ExpertPlacement, cfg: DcommConfig) -> jax.Array:
    sliced, t, d, cap, s = res.state
    e_local = placement.experts_per_lane
    cs = cap // s
    out = expert_out.reshape(placement.ep, e_local, s, cs, d).transpose(
        2, 0, 1, 3, 4)                                       # (S, EP, El, Cs, d)

    def body(y, xs):
        out_s, src_s, gate_s = xs
        returned = pipe_return_issue(out_s, placement, cfg)
        return pipe_return_consume(y, returned, src_s, gate_s, t), None

    y, _ = jax.lax.scan(body, jnp.zeros((t, d), expert_out.dtype),
                        (out, sliced.src, sliced.gate))
    return y


# ======================================================================
# fused_hier
# ======================================================================

def hier_dispatch(x: jax.Array, A: jax.Array, gates: jax.Array,
                  placement: ExpertPlacement, cfg: DcommConfig,
                  assignment: jax.Array | None = None) -> DispatchResult:
    t, d = x.shape
    k = A.shape[1]
    e_local = placement.experts_per_lane
    ns, n_nodes = placement.node_size, placement.n_nodes
    # expected rows per destination *rank* at stage 1: distinct nodes per token
    # <= min(k, n_nodes); conservative envelope k.
    c1 = _cap(t * min(k, n_nodes) / placement.ep, cfg.capacity_factor)
    c2 = _cap(t * k * ns / (placement.ep * ns * e_local), cfg.capacity_factor)

    my_lane = _lane_index(cfg, placement)
    plan1 = planner_lib.build_hier_plan(A, gates, placement, c1, my_lane, assignment)

    # ---- stage 1: node-level forwarding (dedup, slow tier) -----------------
    buf1 = kops.segment_gather(x, plan1.src_of_slot)         # (EP*C1, d)
    me = plan1.meta_expert                                   # (EP*C1, K)
    mg = plan1.meta_gate
    if cfg.pod_axis is not None:
        npod = jax.lax.axis_size(cfg.pod_axis)

        def _ex(v):
            v = v.reshape((npod, placement.ep // npod, c1) + v.shape[2:])
            v = jax.lax.all_to_all(v, cfg.model_axis, 1, 1, tiled=True)
            v = jax.lax.all_to_all(v, cfg.pod_axis, 0, 0, tiled=True)
            return v.reshape((placement.ep * c1,) + v.shape[3:])
    else:
        def _ex(v):
            v = v.reshape((placement.ep, c1) + v.shape[2:])
            v = jax.lax.all_to_all(v, cfg.model_axis, 0, 0, tiled=True)
            return v.reshape((placement.ep * c1,) + v.shape[2:])

    buf1 = _ex(buf1.reshape(placement.ep, c1, d))
    me = _ex(me.reshape(placement.ep, c1, k))
    mg = _ex(mg.reshape(placement.ep, c1, k))

    # ---- stage 2: expert-level distribution (fast tier, expansion) ---------
    plan2 = planner_lib.build_stage2_plan(me, mg, ns, e_local, c2)
    buf2 = kops.segment_gather(buf1, plan2.src_of_slot)      # (ns*E_local*C2, d)
    g2 = plan2.gate_of_slot                                  # (ns*E_local*C2,)

    groups = None
    if cfg.pod_axis is None and ns != placement.ep:
        groups = _node_groups(placement.ep, ns)
    buf2 = buf2.reshape(ns, e_local * c2, d)
    g2 = g2.reshape(ns, e_local * c2)
    buf2 = jax.lax.all_to_all(buf2, cfg.model_axis, 0, 0, tiled=True,
                              axis_index_groups=groups)
    g2 = jax.lax.all_to_all(g2, cfg.model_axis, 0, 0, tiled=True,
                            axis_index_groups=groups)
    expert_rows = buf2.reshape(ns, e_local, c2, d)
    row_gates = g2.reshape(ns, e_local, c2)
    # stage-1 drops are sender-local; stage-2 drops happen on the forwarder
    # after the slow-tier exchange (both were silent before)
    return DispatchResult(expert_rows, row_gates,
                          (plan1, plan2, t, d, c1, c2, groups),
                          plan1.dropped + plan2.slots.dropped())


def hier_combine(expert_out: jax.Array, res: DispatchResult,
                 placement: ExpertPlacement, cfg: DcommConfig) -> jax.Array:
    plan1, plan2, t, d, c1, c2, groups = res.state
    e_local = placement.experts_per_lane
    ns = placement.node_size
    # gate on the expert lane, then return over the fast tier
    out = expert_out * res.row_gates[..., None].astype(expert_out.dtype)
    out = out.reshape(ns, e_local * c2, d)
    out = jax.lax.all_to_all(out, cfg.model_axis, 0, 0, tiled=True,
                             axis_index_groups=groups)
    out = out.reshape(ns * e_local * c2, d)
    # forwarder pre-combine: sum this node's expert partials per stage-1 row
    part = kops.segment_scatter_add(
        out, plan2.src_of_slot, jnp.ones(out.shape[:1], jnp.float32),
        placement.ep * c1)
    # return over the slow tier (deduplicated bytes both directions)
    if cfg.pod_axis is not None:
        npod = jax.lax.axis_size(cfg.pod_axis)
        part = part.reshape(npod, placement.ep // npod, c1, d)
        part = jax.lax.all_to_all(part, cfg.pod_axis, 0, 0, tiled=True)
        part = jax.lax.all_to_all(part, cfg.model_axis, 1, 1, tiled=True)
        part = part.reshape(placement.ep * c1, d)
    else:
        part = part.reshape(placement.ep, c1, d)
        part = jax.lax.all_to_all(part, cfg.model_axis, 0, 0, tiled=True)
        part = part.reshape(placement.ep * c1, d)
    # origin: per-node partials land in my stage-1 slots; gates were applied
    # at the expert, dedup handled by the forwarder pre-combine.
    return kops.segment_scatter_add(
        part, plan1.src_of_slot, jnp.ones(part.shape[:1], jnp.float32), t)


# ======================================================================
# disagg — the paper's §2.3 baseline (materialised sort passes)
# ======================================================================

def disagg_dispatch(x: jax.Array, A: jax.Array, gates: jax.Array,
                    placement: ExpertPlacement, cfg: DcommConfig) -> DispatchResult:
    t, d = x.shape
    k = A.shape[1]
    e_local = placement.experts_per_lane
    cap_lane = _cap(t * k / placement.ep, cfg.capacity_factor)
    cap_e = _cap(t * k / (placement.ep * e_local), cfg.capacity_factor)

    from repro.core.routing import balanced_replica_choice
    replica = balanced_replica_choice(A, placement)
    lane = placement.lane_of_expert(A, replica).reshape(-1)      # (T*K,)
    eloc = placement.local_expert_index(A, replica).reshape(-1)
    tok = jnp.broadcast_to(jnp.arange(t, dtype=I32)[:, None], A.shape).reshape(-1)

    # pass 1: materialised sort-by-destination-rank (the pre-a2a permutation)
    order = jnp.argsort(lane, stable=True)
    xs = jnp.take(x, jnp.take(tok, order), axis=0)               # (T*K, d) pass
    lane_s, eloc_s = jnp.take(lane, order), jnp.take(eloc, order)

    # pass 2: pack into per-lane capacity buffer (device-major layout)
    from repro.core.descriptors import build_slot_table
    st = build_slot_table(lane_s, placement.ep, cap_lane)
    inv = jnp.full((placement.ep * cap_lane,), -1, I32).at[
        drop_neg(st.slot, placement.ep * cap_lane)].set(
        jnp.arange(t * k, dtype=I32), mode="drop")
    buf = gather_rows(xs, inv)                                   # (EP*cap, d) pass
    meta = jnp.full((placement.ep * cap_lane,), -1, I32).at[
        drop_neg(st.slot, placement.ep * cap_lane)].set(eloc_s, mode="drop")

    buf = jax.lax.all_to_all(buf.reshape(placement.ep, cap_lane, d),
                             cfg.model_axis, 0, 0, tiled=True)
    meta = jax.lax.all_to_all(meta.reshape(placement.ep, cap_lane),
                              cfg.model_axis, 0, 0, tiled=True)
    buf = buf.reshape(placement.ep * cap_lane, d)
    meta = meta.reshape(placement.ep * cap_lane)

    # pass 3: receiver-side materialised sort-by-expert + repack
    order2 = jnp.argsort(jnp.where(meta >= 0, meta, e_local), stable=True)
    xr = jnp.take(buf, order2, axis=0)                           # pass
    meta_r = jnp.take(meta, order2)
    st2 = build_slot_table(meta_r, e_local, cap_e * placement.ep)
    inv2 = jnp.full((e_local * cap_e * placement.ep,), -1, I32).at[
        drop_neg(st2.slot, e_local * cap_e * placement.ep)].set(
        jnp.arange(meta_r.shape[0], dtype=I32), mode="drop")
    ebuf = gather_rows(xr, inv2).reshape(1, e_local, cap_e * placement.ep, d)
    state = (order, st, order2, st2, inv2, t, d, k, cap_lane, cap_e)
    return DispatchResult(ebuf, None, state, st.dropped() + st2.dropped())


def disagg_combine(expert_out: jax.Array, res: DispatchResult,
                   placement: ExpertPlacement, cfg: DcommConfig,
                   gates: jax.Array) -> jax.Array:
    order, st, order2, st2, inv2, t, d, k, cap_lane, cap_e = res.state
    e_local = placement.experts_per_lane
    flat = expert_out.reshape(e_local * cap_e * placement.ep, d)
    # inverse pass 3: sorted row i lives at expert-buffer slot st2.slot[i] and
    # came from receive-buffer row order2[i]
    vals = jnp.where((st2.slot >= 0)[:, None],
                     jnp.take(flat, jnp.maximum(st2.slot, 0), axis=0), 0)
    back = jnp.zeros((placement.ep * cap_lane, d), flat.dtype).at[order2].add(vals)
    back = jax.lax.all_to_all(back.reshape(placement.ep, cap_lane, d),
                              cfg.model_axis, 0, 0, tiled=True)
    back = back.reshape(placement.ep * cap_lane, d)
    # inverse passes 2+1: unpack, unsort, weighted combine
    srt = gather_rows(back, st.slot)                             # (T*K, d) sorted order
    unsrt = jnp.zeros((t * k, d), srt.dtype).at[order].set(srt)  # pass
    w = gates.reshape(-1, 1).astype(unsrt.dtype)
    y = (unsrt * w).reshape(t, k, d).sum(axis=1)
    return y


# ======================================================================
# ragged — TPU production engine (true FUSCO descriptor semantics)
# ======================================================================

class RaggedDescriptors(NamedTuple):
    """Sender-side ragged_all_to_all descriptors from a flat plan.

      * ``compact_src``  — (R,) source token row per COMPACT send-buffer row
        (dense slot layout squeezed; -1 tail padding).  This is the sender
        segment-descriptor list of the paper: row i of the wire buffer is
        token ``compact_src[i]``.
      * ``compact_gate`` — (R,) combine weight aligned with ``compact_src``
        (what the reverse exchange scatter-adds home with).
      * ``input_offsets``/``send_sizes`` — per destination lane, the classic
        (address, size) pair over the compact buffer.

    The receiver-side placement (``output_offsets``) is the receiver's
    cumulative layout, exchanged with the counts at runtime — the paper's
    receiver descriptor, named by the sender (§3.2).
    """
    compact_src: jax.Array
    compact_gate: jax.Array
    input_offsets: jax.Array
    send_sizes: jax.Array


def build_ragged_descriptors(plan: planner_lib.FlatPlan,
                             placement: ExpertPlacement,
                             cap: int) -> RaggedDescriptors:
    e_local = placement.experts_per_lane
    counts = jnp.minimum(plan.slots.counts.reshape(placement.ep, e_local), cap)
    send_sizes = counts.sum(axis=1).astype(I32)                 # (EP,)
    input_offsets = jnp.concatenate(
        [jnp.zeros((1,), I32), jnp.cumsum(send_sizes)[:-1].astype(I32)])
    # squeeze the dense slot table into wire order (group-major, no padding)
    occupied = plan.src_of_slot >= 0
    order = jnp.argsort(~occupied, stable=True)                 # occupied first
    # rows stay in slot order within the occupied prefix because argsort is
    # stable — exactly (lane-major, expert-major, arrival-order)
    in_prefix = jnp.arange(order.shape[0]) < occupied.sum()
    compact_src = jnp.where(
        in_prefix, jnp.take(plan.src_of_slot, order), -1).astype(I32)
    compact_gate = jnp.where(
        in_prefix, jnp.take(plan.gate_of_slot, order),
        0).astype(plan.gate_of_slot.dtype)
    return RaggedDescriptors(compact_src, compact_gate, input_offsets,
                             send_sizes)


def ragged_reverse_descriptors(input_offsets: jax.Array, send_sizes: jax.Array,
                               recv_offsets: jax.Array, recv_sizes: jax.Array,
                               peer_input_offsets: jax.Array):
    """Invert a ragged exchange's descriptors for the combine direction.

    The reverse exchange swaps the send/recv roles: what this lane received
    from lane p (``recv_offsets[p]``/``recv_sizes[p]``) it now sends back,
    landing at lane p's original compact-buffer segment — whose start is p's
    forward ``input_offsets`` entry for us, i.e. the all_to_all-exchanged
    ``peer_input_offsets``.  Returns the reverse
    (input_offsets, send_sizes, output_offsets, recv_sizes) quadruple.
    """
    return recv_offsets, recv_sizes, peer_input_offsets, send_sizes


def _a2a_vec(v: jax.Array, ep: int, axis) -> jax.Array:
    """Exchange one scalar per peer over the EP axis."""
    return jax.lax.all_to_all(v.reshape(ep, 1), axis, 0, 0,
                              tiled=True).reshape(ep)


def ragged_dispatch(x: jax.Array, A: jax.Array, gates: jax.Array,
                    placement: ExpertPlacement, cfg: DcommConfig) -> DispatchResult:
    """True ragged engine: no capacity padding on the wire.  TPU-only — the
    dry-run verified XLA:CPU rejects ragged-all-to-all (ThunkEmitter), so CPU
    tests exercise :func:`build_ragged_descriptors` structurally."""
    t, d = x.shape
    k = A.shape[1]
    e_local = placement.experts_per_lane
    cap = _cap(t * k / (placement.ep * e_local), cfg.capacity_factor)
    plan = planner_lib.build_flat_plan(A, gates, placement, cap)
    desc = build_ragged_descriptors(plan, placement, cap)
    offs, send_sizes = desc.input_offsets, desc.send_sizes

    send_buf = gather_rows(x, desc.compact_src)                 # fused stage copy
    # exchange counts, derive receiver placement (paper: sender names the
    # receiver offsets — they are the receiver's cumulative layout)
    recv_sizes = _a2a_vec(send_sizes, placement.ep, cfg.model_axis)
    recv_offs = jnp.concatenate([jnp.zeros((1,), I32),
                                 jnp.cumsum(recv_sizes)[:-1].astype(I32)])
    out_offsets = _a2a_vec(recv_offs, placement.ep, cfg.model_axis)
    out_buf = jnp.zeros((placement.ep * e_local * cap, d), x.dtype)
    landed = jax.lax.ragged_all_to_all(
        send_buf, out_buf, offs, send_sizes, out_offsets, recv_sizes,
        axis_name=cfg.model_axis)
    return DispatchResult(landed.reshape(1, 1, placement.ep * e_local * cap, d),
                          None, (desc, t, d, cap, recv_offs, recv_sizes),
                          plan.dropped)


def ragged_combine(expert_out: jax.Array, res: DispatchResult,
                   placement: ExpertPlacement, cfg: DcommConfig) -> jax.Array:
    """Reverse ragged exchange + weighted scatter-add home (TPU-only, like
    dispatch).  The reverse descriptors are the forward ones with send/recv
    roles swapped (:func:`ragged_reverse_descriptors`); returned compact rows
    line up with ``compact_src``/``compact_gate`` by construction, so the
    combine is one fused weighted scatter-add — no unpacking pass.
    """
    desc, t, d, cap, recv_offs, recv_sizes = res.state
    ep = placement.ep
    # each peer needs our forward input_offsets to know where its return
    # segment lands in our compact buffer — one more descriptor exchange.
    peer_offs = _a2a_vec(desc.input_offsets, ep, cfg.model_axis)
    rev = ragged_reverse_descriptors(desc.input_offsets, desc.send_sizes,
                                     recv_offs, recv_sizes, peer_offs)
    rev_in_offs, rev_send_sizes, rev_out_offs, rev_recv_sizes = rev
    flat = expert_out.reshape(-1, d)
    back_buf = jnp.zeros((desc.compact_src.shape[0], d), flat.dtype)
    back = jax.lax.ragged_all_to_all(
        flat, back_buf, rev_in_offs, rev_send_sizes, rev_out_offs,
        rev_recv_sizes, axis_name=cfg.model_axis)
    w = desc.compact_gate[:, None].astype(back.dtype)
    return jnp.zeros((t, d), back.dtype).at[
        drop_neg(desc.compact_src, t)].add(back * w, mode="drop")
