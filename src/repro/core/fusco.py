"""FUSCO public API — drop-in MoE shuffle + expert compute.

The integration surface the paper describes (§4: "a thin adaptation layer
bridges the framework's token-routing path with our planner and dComm
primitive"): a model layer calls :func:`moe_shuffle_ffn` inside a shard_map
over the expert-parallel axis and gets back combined expert outputs in the
original token layout.  Engine choice, hierarchy and balancer are config.

Also provides :func:`dense_moe_reference` — the per-token dense oracle used by
tests to validate every engine bit-for-bit (up to dtype tolerance) — and the
cross-layer stream API :func:`pipe_layer_stream` / :func:`layer_stream` /
:func:`interleaved_layer_stream`: N consecutive MoE layers chained through one
pipelined schedule where the combine of layer i overlaps the dispatch of
layer i+1 (MegaScale-MoE-style), optionally with K token micro-batches
interleaved round-robin through it so micro-batch j+1's router + expert FFN
fills micro-batch j's boundary window.  :func:`stream_dense_reference` is the
stacked dense oracle for both (the stream is order-preserving per token, so
the oracle is interleave-invariant).

:func:`tx_layer_stream` extends the stream to ATTENTION-separated layers —
real transformer blocks: N parallel attention+MoE blocks
(``h + attn(ln1 h) + moe(ln2 h)``) through one schedule, the MoE tail combine
of each layer riding across that layer's attention block (the attention
collectives — the k/v all-gather over the EP axes — live inside the island,
:func:`tx_attention`).  Oracle: :func:`tx_dense_reference`.  See DESIGN.md
§attention-stream.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import dcomm
from repro.core.dcomm import DcommConfig, DispatchResult
from repro.core.routing import (ExpertPlacement, router_logits, top_k_routing)
from repro.kernels import ops as kops
from repro.layers.attention import gqa_project
from repro.layers.common import apply_rope, rms_norm


@jax.named_scope("moe.expert_ffn")
def swiglu_experts(rows: jax.Array, w1: jax.Array, w3: jax.Array,
                   w2: jax.Array) -> jax.Array:
    """Grouped SwiGLU FFN consuming the landed buffer in place.

    rows: (S, E_local, C, d); w1/w3: (E_local, d, f); w2: (E_local, f, d).
    The local-expert dimension is a batch dim — no data rearrangement is
    required because dispatch landed rows expert-grouped.  Routed through
    ``kernels.ops.fused_swiglu``: with ``use_pallas()`` the whole
    gate/up/SiLU/down chain is ONE Pallas kernel whose (C, f) hidden
    activations never round-trip HBM; otherwise the jnp einsum reference.
    """
    return kops.fused_swiglu(rows, w1, w3, w2)


@jax.named_scope("moe.dispatch")
def dispatch(x, A, gates, placement: ExpertPlacement, cfg: DcommConfig,
             assignment=None) -> DispatchResult:
    if cfg.engine == "fused_flat":
        if cfg.dedup:
            return dcomm.dedup_dispatch(x, A, gates, placement, cfg)
        return dcomm.flat_dispatch(x, A, gates, placement, cfg)
    if cfg.engine == "fused_pipe":
        return dcomm.pipe_dispatch(x, A, gates, placement, cfg)
    if cfg.engine == "fused_hier":
        return dcomm.hier_dispatch(x, A, gates, placement, cfg,
                                   assignment if cfg.use_balancer else None)
    if cfg.engine == "disagg":
        return dcomm.disagg_dispatch(x, A, gates, placement, cfg)
    if cfg.engine == "ragged":
        return dcomm.ragged_dispatch(x, A, gates, placement, cfg)
    raise ValueError(f"unknown engine {cfg.engine!r}")


@jax.named_scope("moe.combine")
def combine(expert_out, res: DispatchResult, placement, cfg: DcommConfig,
            gates=None) -> jax.Array:
    if cfg.engine == "fused_flat":
        if cfg.dedup:
            return dcomm.dedup_combine(expert_out, res, placement, cfg)
        return dcomm.flat_combine(expert_out, res, placement, cfg)
    if cfg.engine == "fused_pipe":
        return dcomm.pipe_combine(expert_out, res, placement, cfg)
    if cfg.engine == "fused_hier":
        return dcomm.hier_combine(expert_out, res, placement, cfg)
    if cfg.engine == "disagg":
        return dcomm.disagg_combine(expert_out, res, placement, cfg, gates)
    if cfg.engine == "ragged":
        return dcomm.ragged_combine(expert_out, res, placement, cfg)
    raise ValueError(f"unknown engine {cfg.engine!r}")


def shuffle_ffn(x: jax.Array, A: jax.Array, gates: jax.Array, w1: jax.Array,
                w3: jax.Array, w2: jax.Array, placement: ExpertPlacement,
                cfg: DcommConfig,
                assignment: jax.Array | None = None) -> jax.Array:
    """Shuffle + grouped FFN + combine for pre-computed routing.

    For ``fused_pipe`` this is the fully fused sliced pipeline — the grouped
    FFN runs per capacity slice inside the communication loop; the split
    dispatch()/combine() path remains available for comm-only benchmarking.
    """
    if cfg.engine == "fused_pipe":
        return dcomm.pipe_shuffle_ffn(
            x, A, gates, lambda rows: swiglu_experts(rows, w1, w3, w2),
            placement, cfg)
    res = dispatch(x, A, gates, placement, cfg, assignment)
    out = swiglu_experts(res.expert_rows, w1, w3, w2)
    return combine(out, res, placement, cfg, gates)


def moe_shuffle_ffn(x: jax.Array, w_router: jax.Array, w1: jax.Array,
                    w3: jax.Array, w2: jax.Array, placement: ExpertPlacement,
                    cfg: DcommConfig, top_k: int,
                    assignment: jax.Array | None = None,
                    norm_topk: bool = True) -> jax.Array:
    """Full fused MoE block: route → dispatch → grouped FFN → combine.

    Runs inside shard_map; ``x`` is this shard's (T_local, d) tokens, weights
    are this lane's expert slices (E_local, d, f)/(E_local, f, d); the router
    weight is replicated.
    """
    with jax.named_scope("moe.route"):
        logits = router_logits(x, w_router)
        A, gates = top_k_routing(logits, top_k, normalize=norm_topk)
    return shuffle_ffn(x, A, gates.astype(x.dtype), w1, w3, w2, placement,
                       cfg, assignment)


# ---------------------------------------------------------------------------
# Cross-layer pipelined MoE stream
# ---------------------------------------------------------------------------

def _stream_layer_io(h, lp, top_k, norm_topk):
    """Shared pre-shuffle work of one stream layer: optional pre-norm +
    routing.  ``lp`` is the layer's parameter dict (ln may be None)."""
    u = rms_norm(h, lp["ln"]) if lp.get("ln") is not None else h
    logits = router_logits(u, lp["router"])
    A, gates = top_k_routing(logits, top_k, normalize=norm_topk)
    return u, A, gates.astype(h.dtype)


def _stack_stream_params(w_router, w1, w3, w2, ln):
    """Per-layer xs for the layer scan; ln folded in when present."""
    lp = {"router": w_router, "w1": w1, "w3": w3, "w2": w2}
    if ln is not None:
        lp["ln"] = ln
    return lp


def pipe_layer_stream(x: jax.Array, w_router: jax.Array, w1: jax.Array,
                      w3: jax.Array, w2: jax.Array,
                      placement: ExpertPlacement, cfg: DcommConfig,
                      top_k: int, ln: jax.Array | None = None,
                      norm_topk: bool = True, traffic=None, observe=None):
    """Chain N consecutive MoE layers through ONE pipelined schedule.

    ``w_router``: (N, d, E) replicated; ``w1``/``w3``: (N, E_local, d, f) and
    ``w2``: (N, E_local, f, d) this lane's expert slices; ``ln``: optional
    (N, d) pre-norm scales.  Each layer computes the residual update
    ``h <- h + moe_l(norm_l(h))``.

    What the stream changes vs. one ``pipe_shuffle_ffn`` per layer:

      * the per-layer *program* barrier is gone — layer l's shuffle ends
        with its tail slice's combine exchange still in flight
        (:class:`dcomm.PipeTail`) and the deferred scatter-add lands in
        layer l+1's prologue, so the boundary is a single async-ready
        exchange rather than a fully materialised layer output;
      * the slice count is chosen JOINTLY for the whole chain via
        :func:`pipesim.plan_layer_stream` (all layers must share one static
        slice geometry so the carried tail shape is invariant);
      * each layer's residual seeds the accumulator directly (``y0=h``),
        fusing the residual add into the combine scatter-add.

    Overlap status: in this K=1 *pure* MoE chain, layer l+1's router reads
    the completed ``h``, so the deferred tail has no tail-independent
    compute to hide behind at the boundary — the structure alone does not
    fill the window.  The filled version is
    :func:`interleaved_layer_stream`: K>=2 token micro-batches round-robin
    through the same schedule, micro-batch j+1's router + grouped FFN
    landing exactly in micro-batch j's boundary window
    (``pipesim.simulate_interleaved_stream`` quantifies the bubble-fraction
    reduction).  Still open: streaming through attention-separated MoE
    layers (the island must own the attention collectives) and the
    linear-router trick (router logits are linear in ``h``, so at
    ``ln=None`` partial-accumulator logits plus a tail-delta correction
    would let routing start before the tail lands) — see ROADMAP.md.

    ``traffic``: optional per-layer stacked ``traffic.TrafficState``
    (leading ``(N,)`` dim) riding the layer scan as xs, each layer's slice
    folded via ``observe(state, A)`` (a caller-built closure over placement /
    lane / psum axes — keeps the traffic subsystem out of the engine core)
    and returned updated as ys.  With it the function returns
    ``(h, new_traffic)`` instead of ``h`` — this is what lets the
    load-adaptive re-layout act on the stream family too.

    Runs inside shard_map over the EP axis/axes, like every engine entry
    point.  Gradient-parity with :func:`stream_dense_reference` is covered by
    ``tests/test_engine_grads.py``.
    """
    if cfg.engine != "fused_pipe":
        raise ValueError(
            f"pipe_layer_stream requires engine='fused_pipe', got {cfg.engine!r}")
    t, d = x.shape
    n_layers = w_router.shape[0]
    cap, s = dcomm.pipe_geometry(t, top_k, d, x.dtype.itemsize, placement,
                                 cfg, n_layers=n_layers)
    cfg = dataclasses.replace(cfg, pipe_slices=s)     # freeze the joint plan
    cs = cap // s

    def layer(carry, xs):
        lp, tr = xs if traffic is not None else (xs, None)
        h, tail = carry
        h = dcomm.pipe_tail_consume(h, tail, t)       # land layer l-1's tail
        u, A, gates = _stream_layer_io(h, lp, top_k, norm_topk)
        if tr is not None:
            tr = observe(tr, A)
        ffn = lambda rows: swiglu_experts(rows, lp["w1"], lp["w3"], lp["w2"])
        y, tail = dcomm.pipe_shuffle_ffn_stream(u, A, gates, ffn, placement,
                                                cfg, y0=h)    # residual seed
        return (y, tail), tr

    lps = _stack_stream_params(w_router, w1, w3, w2, ln)
    tail0 = dcomm.pipe_empty_tail(placement, cs, d, x.dtype, x.dtype)
    (h, tail), new_traffic = jax.lax.scan(
        layer, (x, tail0), lps if traffic is None else (lps, traffic))
    h = dcomm.pipe_tail_consume(h, tail, t)           # epilogue: last tail
    return h if traffic is None else (h, new_traffic)


def interleaved_layer_stream(x: jax.Array, w_router: jax.Array,
                             w1: jax.Array, w3: jax.Array, w2: jax.Array,
                             placement: ExpertPlacement, cfg: DcommConfig,
                             top_k: int, ln: jax.Array | None = None,
                             norm_topk: bool = True, interleave: int = 2,
                             traffic=None, observe=None):
    """K token micro-batches round-robin through ONE cross-layer schedule.

    ``x`` (t, d) is split into ``interleave`` contiguous micro-batch lanes of
    t/K tokens; per layer, lane j's shuffle (router → sliced dispatch/FFN →
    tail combine issued) is followed by lane j+1's, and lane j's deferred
    tail (:class:`dcomm.PipeTail`) lands only in lane j's next-layer
    prologue.  That turns the structural window :func:`pipe_layer_stream`
    opens into a *filled* one: while lane j's tail combine exchange is on
    the wire, lanes j+1..K-1 run router + grouped FFN — tail-independent
    compute with no data dependence on the in-flight exchange, which XLA's
    async collectives (TPU) can therefore overlap.  K tails ride the layer
    scan carry stacked on a leading lane axis; weights are shared across
    lanes (same layer), so the scan still compiles one layer body.

    Capacity and the slice count are planned per LANE (t/K tokens) with the
    schedule-aware knee from ``pipesim.plan_interleaved_stream``; all lanes
    and layers share one static slice geometry so every carried tail has the
    same shape.  K=2 already suffices on paper-scale geometries: one lane's
    FFN + router time exceeds the tail exchange time (DESIGN.md
    §stream-interleave), and larger K only adds per-slice overhead.

    The result is bit-identical (up to scatter-add rounding) to
    :func:`pipe_layer_stream` on the same ``x``, because lanes never
    interact — the oracle is the same :func:`stream_dense_reference`.
    ``interleave=1`` degenerates to exactly :func:`pipe_layer_stream`.

    ``traffic``/``observe``: as in :func:`pipe_layer_stream`; each layer
    folds ONE observation covering all K lanes' routing (the lanes' token-
    expert matrices concatenated), so the EMA semantics match the
    non-interleaved stream step for step.
    """
    if cfg.engine != "fused_pipe":
        raise ValueError(
            "interleaved_layer_stream requires engine='fused_pipe', "
            f"got {cfg.engine!r}")
    kk = max(1, int(interleave))
    t, d = x.shape
    if t % kk != 0:
        raise ValueError(
            f"interleave={kk} must divide the island's {t} tokens "
            "(micro-batch lanes need identical static shapes)")
    tc = t // kk
    n_layers = w_router.shape[0]
    cap, s = dcomm.pipe_geometry(tc, top_k, d, x.dtype.itemsize, placement,
                                 cfg, n_layers=n_layers, interleave=kk)
    cfg = dataclasses.replace(cfg, pipe_slices=s)     # freeze the joint plan
    cs = cap // s

    def layer(carry, xs):
        lp, tr = xs if traffic is not None else (xs, None)
        hs, tails = carry
        ffn = lambda rows: swiglu_experts(rows, lp["w1"], lp["w3"], lp["w2"])
        new_h, new_tails, As = [], [], []
        for j in range(kk):               # round-robin over micro-batch lanes
            tail = jax.tree.map(lambda a, j=j: a[j], tails)
            h = dcomm.pipe_tail_consume(hs[j], tail, tc)   # lane j's prologue
            u, A, gates = _stream_layer_io(h, lp, top_k, norm_topk)
            y, tail = dcomm.pipe_shuffle_ffn_stream(u, A, gates, ffn,
                                                    placement, cfg, y0=h)
            new_h.append(y)
            new_tails.append(tail)
            As.append(A)
        if tr is not None:
            tr = observe(tr, jnp.concatenate(As, axis=0))
        return ((jnp.stack(new_h),
                 jax.tree.map(lambda *a: jnp.stack(a), *new_tails)), tr)

    tails0 = dcomm.pipe_empty_tails(placement, cs, d, x.dtype, x.dtype, kk)
    lps = _stack_stream_params(w_router, w1, w3, w2, ln)
    (hs, tails), new_traffic = jax.lax.scan(
        layer, (x.reshape(kk, tc, d), tails0),
        lps if traffic is None else (lps, traffic))
    # epilogue: land every lane's final tail
    outs = [dcomm.pipe_tail_consume(hs[j],
                                    jax.tree.map(lambda a, j=j: a[j], tails),
                                    tc)
            for j in range(kk)]
    h = jnp.concatenate(outs, axis=0)
    return h if traffic is None else (h, new_traffic)


# ---------------------------------------------------------------------------
# Attention-separated stream (moe_tx): real transformer blocks inside the
# fused schedule
# ---------------------------------------------------------------------------

def tx_attention(h: jax.Array, lp, pos_q: jax.Array, pos_k: jax.Array, *,
                 n_heads: int, n_kv: int, head_dim: int,
                 rope_theta: float = 1e6, ep_axes=(), return_kv: bool = False):
    """Attention sub-layer of a ``moe_tx`` parallel block.

    ``h`` is (b, s_local, d) — this shard's batch rows over its sequence
    chunk.  Inside the island ``ep_axes`` names the mesh axes the sequence is
    sharded over: q/k/v are projected from the local rows, RoPE'd at their
    absolute positions (``pos_q``), and k/v are **all-gathered over the EP
    axes** — these are the attention collectives the island owns, which is
    what lets a :class:`dcomm.PipeTail` stay in flight across the attention
    block instead of forcing an island boundary (and its program barrier)
    between every MoE layer.  With empty ``ep_axes`` this is the plain
    full-sequence attention the oracle uses.  ``return_kv`` additionally
    returns the gathered, RoPE'd (k, v) — identical on every EP lane — for
    prefill cache extraction.
    """
    u = rms_norm(h, lp["ln1"])
    q, k, v = gqa_project(u, lp["wq"], lp["wk"], lp["wv"], n_heads, n_kv,
                          head_dim)
    q = apply_rope(q, pos_q, rope_theta)
    k = apply_rope(k, pos_q, rope_theta)
    for ax in reversed(tuple(ep_axes)):      # inner axis first: global order
        k = jax.lax.all_gather(k, ax, axis=1, tiled=True)
        v = jax.lax.all_gather(v, ax, axis=1, tiled=True)
    # position-safe block-skipping flash (Pallas when use_pallas(), lax flash
    # otherwise): the shifted pos_q chunk masks/skips from actual per-block
    # position bounds, so the island no longer needs the O(S²) reference core.
    a = kops.flash_attention(q, k, v, pos_q, pos_k, causal=True)
    b, s = h.shape[0], h.shape[1]
    out = a.reshape(b, s, n_heads * head_dim) @ lp["wo"]
    if return_kv:
        return out, (k, v)
    return out


def _tx_attn_cost_s(tc: int, s_l: int, bc: int, s_glob: int, n_heads: int,
                    head_dim: int, itemsize: int, cfg: DcommConfig) -> float:
    """Planning proxy for the attention window filler: the byte volume the
    attention block moves through the staging tier (q/k/v/o activations +
    f32 score/prob tiles), converted to seconds at the config's staging
    bandwidth.  Deliberately coarse — it only has to place the pipesim knee,
    not predict wall clock."""
    attn_bytes = (4.0 * tc * n_heads * head_dim * itemsize
                  + 2.0 * 4.0 * bc * n_heads * s_l * s_glob)
    return attn_bytes / cfg.pipe_stage_bw


def tx_layer_stream(x: jax.Array, positions: jax.Array, params, placement,
                    cfg: DcommConfig, top_k: int, *, n_heads: int, n_kv: int,
                    head_dim: int, rope_theta: float = 1e6,
                    norm_topk: bool = True, stream: bool = True,
                    interleave: int = 1, traffic=None, observe=None,
                    return_kv: bool = False):
    """Chain N attention+MoE transformer blocks through ONE fused schedule.

    ``x`` is (b, s_local, d) — this shard's rows (batch data-sharded by the
    caller, sequence sharded over the EP axes); ``positions`` the full (S,)
    absolute positions; ``params`` the stacked per-layer dict
    ``{ln1, wq, wk, wv, wo, ln2, router, w1, w3, w2}`` (attention weights
    replicated, expert weights this lane's slices).

    Each layer is a **parallel** transformer block

        ``h <- h + attn(rms_norm(h, ln1)) + moe(rms_norm(h, ln2))``

    (PaLM/GPT-J-style; both branches read the block input), chosen precisely
    because it makes the attention block *tail-independent*: the MoE shuffle
    is issued first and ends with its tail combine exchange in flight
    (:class:`dcomm.PipeTail`), then the attention block — which has no data
    dependence on the in-flight exchange — runs while the tail is on the
    wire, and the tail lands only in the next layer's prologue.  A
    *sequential* block (attention reading the completed MoE output) admits
    no such work at K=1: every op after the MoE needs the tail, which is why
    the pure-MoE chain's window stayed empty (ROADMAP) and why MegaScale-MoE
    gets its window-filling compute precisely from attention.
    ``pipesim.simulate_tx_stream`` models this schedule and quantifies the
    boundary-bubble reduction vs the pure chain.

    Composes with ``interleave=K``: K batch-chunk micro-batch lanes
    round-robin through the schedule as in
    :func:`interleaved_layer_stream`, so lane j's tail additionally rides
    across lanes j+1..K-1's whole blocks (shuffle staging + attention).

    The slice count is chosen jointly for the chain via
    :func:`pipesim.plan_tx_stream` with the attention cost proxy
    (:func:`_tx_attn_cost_s`); ``stream=False`` (or a non-pipelined engine)
    runs the same function with a full per-layer barrier.

    ``traffic``/``observe`` as in :func:`pipe_layer_stream`.  ``return_kv``
    appends the per-layer gathered RoPE'd (k, v) stacks for prefill cache
    extraction.  Returns ``h`` with ``(h, traffic)`` / ``(..., kv)``
    appended per flag.  Gradient-parity with :func:`tx_dense_reference` is
    covered by ``tests/test_engine_grads.py``.
    """
    ep_axes = (tuple(cfg.ep_axis) if isinstance(cfg.ep_axis, (tuple, list))
               else (cfg.ep_axis,))
    b, s_l, d = x.shape
    chunk = dcomm._lane_index(cfg, placement)
    pos_q = jax.lax.dynamic_slice(positions, (chunk * s_l,), (s_l,))
    attn_kw = dict(n_heads=n_heads, n_kv=n_kv, head_dim=head_dim,
                   rope_theta=rope_theta, ep_axes=ep_axes)

    if not (stream and cfg.engine == "fused_pipe"):
        # per-layer-barrier fallback: same parallel blocks, any engine
        def layer(h, xs):
            lp, tr = xs if traffic is not None else (xs, None)
            a = tx_attention(h, lp, pos_q, positions, return_kv=return_kv,
                             **attn_kw)
            kv = None
            if return_kv:
                a, kv = a
            u2 = rms_norm(h, lp["ln2"]).reshape(b * s_l, d)
            logits = router_logits(u2, lp["router"])
            A, gates = top_k_routing(logits, top_k, normalize=norm_topk)
            if tr is not None:
                tr = observe(tr, A)
            y = shuffle_ffn(u2, A, gates.astype(h.dtype), lp["w1"], lp["w3"],
                            lp["w2"], placement, cfg)
            return h + a + y.reshape(b, s_l, d), (tr, kv)

        h, (new_traffic, kv) = jax.lax.scan(
            layer, x, params if traffic is None else (params, traffic))
        out = (h,)
        if traffic is not None:
            out += (new_traffic,)
        if return_kv:
            out += (kv,)
        return out[0] if len(out) == 1 else out

    kk = max(1, int(interleave))
    if b % kk != 0:
        raise ValueError(
            f"interleave={kk} must divide the island's per-shard batch {b} "
            "(micro-batch lanes are batch chunks)")
    bc = b // kk
    tc = bc * s_l
    n_layers = params["router"].shape[0]
    attn_s = _tx_attn_cost_s(tc, s_l, bc, positions.shape[0], n_heads,
                             head_dim, x.dtype.itemsize, cfg)
    cap, ns = dcomm.pipe_geometry(tc, top_k, d, x.dtype.itemsize, placement,
                                  cfg, n_layers=n_layers, interleave=kk,
                                  attn_s=attn_s)
    cfg = dataclasses.replace(cfg, pipe_slices=ns)    # freeze the joint plan
    cs = cap // ns

    def layer(carry, xs):
        lp, tr = xs if traffic is not None else (xs, None)
        hs, tails = carry
        ffn = lambda rows: swiglu_experts(rows, lp["w1"], lp["w3"], lp["w2"])
        new_h, new_tails, As, kfs, vfs = [], [], [], [], []
        for j in range(kk):               # round-robin over micro-batch lanes
            tail = jax.tree.map(lambda a, j=j: a[j], tails)
            ht = dcomm.pipe_tail_consume(hs[j].reshape(tc, d), tail, tc)
            h = ht.reshape(bc, s_l, d)
            # MoE branch issued FIRST: router -> sliced dispatch/FFN -> tail
            # combine exchange, which then rides across the attention below
            u2 = rms_norm(h, lp["ln2"]).reshape(tc, d)
            logits = router_logits(u2, lp["router"])
            A, gates = top_k_routing(logits, top_k, normalize=norm_topk)
            y, tail = dcomm.pipe_shuffle_ffn_stream(
                u2, A, gates.astype(h.dtype), ffn, placement, cfg, y0=ht)
            # attention branch reads the block INPUT h (parallel block):
            # tail-independent compute placed exactly in the tail's window
            a = tx_attention(h, lp, pos_q, positions, return_kv=return_kv,
                             **attn_kw)
            if return_kv:
                a, (kf, vf) = a
                kfs.append(kf)
                vfs.append(vf)
            new_h.append(y.reshape(bc, s_l, d) + a)
            new_tails.append(tail)
            As.append(A)
        if tr is not None:
            tr = observe(tr, jnp.concatenate(As, axis=0))
        kv = ((jnp.concatenate(kfs, 0), jnp.concatenate(vfs, 0))
              if return_kv else None)
        return ((jnp.stack(new_h),
                 jax.tree.map(lambda *a: jnp.stack(a), *new_tails)),
                (tr, kv))

    tails0 = dcomm.pipe_empty_tails(placement, cs, d, x.dtype, x.dtype, kk)
    (hs, tails), (new_traffic, kv) = jax.lax.scan(
        layer, (x.reshape(kk, bc, s_l, d), tails0),
        params if traffic is None else (params, traffic))
    # epilogue: land every lane's final tail
    outs = [dcomm.pipe_tail_consume(hs[j].reshape(tc, d),
                                    jax.tree.map(lambda a, j=j: a[j], tails),
                                    tc)
            for j in range(kk)]
    h = jnp.concatenate(outs, axis=0).reshape(b, s_l, d)
    out = (h,)
    if traffic is not None:
        out += (new_traffic,)
    if return_kv:
        out += (kv,)
    return out[0] if len(out) == 1 else out


def tx_dense_reference(x: jax.Array, positions: jax.Array, params,
                       top_k: int, *, n_heads: int, n_kv: int, head_dim: int,
                       rope_theta: float = 1e6,
                       norm_topk: bool = True) -> jax.Array:
    """Oracle for the attention-separated stream: the same parallel
    attention+MoE residual chain evaluated with full-sequence attention and
    the per-token dense MoE reference.  ``params`` holds ALL experts per
    layer (w1/w3 ``(N, E, d, f)``, w2 ``(N, E, f, d)``); ``x`` is the full
    (b, S, d) batch."""
    b, s, d = x.shape
    h = x
    for l in range(params["router"].shape[0]):
        lp = jax.tree.map(lambda a, l=l: a[l], params)
        a = tx_attention(h, lp, positions, positions, n_heads=n_heads,
                         n_kv=n_kv, head_dim=head_dim, rope_theta=rope_theta)
        u2 = rms_norm(h, lp["ln2"]).reshape(b * s, d)
        m = dense_moe_reference(u2, lp["router"], lp["w1"], lp["w3"],
                                lp["w2"], top_k, norm_topk=norm_topk)
        h = h + a + m.reshape(b, s, d)
    return h


def layer_stream(x: jax.Array, w_router: jax.Array, w1: jax.Array,
                 w3: jax.Array, w2: jax.Array, placement: ExpertPlacement,
                 cfg: DcommConfig, top_k: int, ln: jax.Array | None = None,
                 norm_topk: bool = True, stream: bool = True,
                 interleave: int = 1, traffic=None, observe=None):
    """Stream dispatch table: the cross-layer pipelined schedule when the
    engine supports it (micro-batch interleaved for ``interleave >= 2``),
    else the per-layer-barrier fallback (each layer a full
    :func:`shuffle_ffn`, any engine; interleaving is a property of the
    pipelined schedule, so the fallback ignores it).  Same layout contract
    and result as :func:`pipe_layer_stream`, including the optional
    ``traffic``/``observe`` threading."""
    if stream and cfg.engine == "fused_pipe":
        if interleave > 1:
            return interleaved_layer_stream(
                x, w_router, w1, w3, w2, placement, cfg, top_k, ln=ln,
                norm_topk=norm_topk, interleave=interleave, traffic=traffic,
                observe=observe)
        return pipe_layer_stream(x, w_router, w1, w3, w2, placement, cfg,
                                 top_k, ln=ln, norm_topk=norm_topk,
                                 traffic=traffic, observe=observe)

    def layer(h, xs):
        lp, tr = xs if traffic is not None else (xs, None)
        u, A, gates = _stream_layer_io(h, lp, top_k, norm_topk)
        if tr is not None:
            tr = observe(tr, A)
        y = shuffle_ffn(u, A, gates, lp["w1"], lp["w3"], lp["w2"], placement,
                        cfg)
        return h + y, tr

    lps = _stack_stream_params(w_router, w1, w3, w2, ln)
    h, new_traffic = jax.lax.scan(layer, x,
                                  lps if traffic is None else (lps, traffic))
    return h if traffic is None else (h, new_traffic)


def stream_dense_reference(x: jax.Array, w_router: jax.Array,
                           w1_all: jax.Array, w3_all: jax.Array,
                           w2_all: jax.Array, top_k: int,
                           ln: jax.Array | None = None,
                           norm_topk: bool = True) -> jax.Array:
    """Oracle for the layer stream: the same residual chain evaluated with
    the per-token dense reference.  ``w*_all`` hold ALL experts per layer:
    (N, E, d, f)/(N, E, f, d)."""
    h = x
    for l in range(w_router.shape[0]):
        u = rms_norm(h, ln[l]) if ln is not None else h
        h = h + dense_moe_reference(u, w_router[l], w1_all[l], w3_all[l],
                                    w2_all[l], top_k, norm_topk=norm_topk)
    return h


def dense_moe_reference(x: jax.Array, w_router: jax.Array, w1_all: jax.Array,
                        w3_all: jax.Array, w2_all: jax.Array, top_k: int,
                        norm_topk: bool = True) -> jax.Array:
    """Oracle: per-token dense evaluation of the selected experts.

    ``w*_all`` hold ALL experts (E, d, f)/(E, f, d).  O(T·K·d·f) — small
    configs only.
    """
    logits = router_logits(x, w_router)
    A, gates = top_k_routing(logits, top_k, normalize=norm_topk)

    def per_token(xt, experts, g):
        def per_k(e, w):
            h = jax.nn.silu(xt @ w1_all[e]) * (xt @ w3_all[e])
            return w * (h @ w2_all[e])
        outs = jax.vmap(per_k)(experts, g.astype(xt.dtype))
        return outs.sum(axis=0)

    return jax.vmap(per_token)(x, A, gates)
