"""MoE layer — FUSCO-integrated expert-parallel feed-forward.

The shard_map island: dense parts of the model run under GSPMD; the token
shuffle runs manually over the expert-parallel axes with the engine picked by
``DcommConfig`` (fused_flat / fused_pipe / fused_hier / disagg / ragged).
This is the "thin adaptation layer" of paper §4.

Three island granularities:

  * :func:`moe_block` — ONE MoE layer per island (norm + residual live
    outside); every layer ends with a full barrier before the next.
  * :func:`stream_moe_layers` — a BLOCK of consecutive MoE layers in one
    island, chained through ``fusco.layer_stream``: with the ``fused_pipe``
    engine the combine of layer i overlaps the dispatch of layer i+1
    (cross-layer stream), so each layer's pre-norm and residual run inside
    the island too.
  * :func:`stream_tx_layers` — a BLOCK of attention+MoE transformer layers
    (parallel blocks) in one island that ALSO owns the attention
    collectives (k/v all-gather over the EP axes): the MoE tail combine of
    each layer rides across its attention block (``fusco.tx_layer_stream``,
    DESIGN.md §attention-stream).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from repro.compat import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.dcomm import DcommConfig, _lane_index
from repro.core.routing import (ExpertPlacement, balanced_replica_choice,
                                router_logits, top_k_routing)
from repro.core import balancer as balancer_lib
from repro.core import fusco
from repro.core import traffic as traffic_lib
from repro.kernels import ops as kops


def moe_block(x: jax.Array, moe_params, *, mesh, placement: ExpertPlacement,
              dcfg: DcommConfig, top_k: int, data_axes=("data",),
              norm_topk: bool = True, fsdp: bool = False,
              traffic: traffic_lib.TrafficState | None = None,
              traffic_decay: float = 0.99,
              traffic_mask: jax.Array | None = None):
    """x: (B, S, d) global. Expert weights sharded over the EP axes.

    Weight layout: w1/w3 (E_lanes, E_local, d, f), w2 (E_lanes, E_local, f, d)
    where E_lanes = placement.ep — lane-major so a plain PartitionSpec shards
    them (replicated experts appear once per hosting lane).

    ``traffic`` threads this layer's online traffic statistics through the
    island (state in, updated state out — like RNG state): the routing matrix
    is folded into the EMA accumulators *inside* the island, and when the
    engine is hierarchical with the balancer on, Algorithm 1 is fed the EMA
    lane-send loads instead of the static balancer-off grouping
    (``balancer.static_assignment`` remains the ``use_balancer=False``
    ablation knob).  Returns ``(y, new_traffic)`` when given, ``y`` otherwise.

    ``traffic_mask``: optional (B, S) bool validity mask (True = a real
    token).  Masked-out positions — serving prefill left-pad slots and
    interleave pad rows — are still ROUTED (static shapes) but no longer
    counted by ``traffic.observe``, so pad traffic cannot skew the EMA the
    re-layout solver acts on.
    """
    ep_axes = dcfg.ep_axis if isinstance(dcfg.ep_axis, (tuple, list)) else (dcfg.ep_axis,)
    ep_axes = tuple(ep_axes)
    x_spec = P(data_axes, ep_axes, None)          # batch over data, seq over EP
    if fsdp:
        # ZeRO-3 expert weights: stored sharded over the data axis, gathered
        # just-in-time inside the island (mixtral-class expert sizes).
        w_spec = P(ep_axes, None, None, "data")
        w2_spec = P(ep_axes, None, "data", None)
    else:
        w_spec = w2_spec = P(ep_axes, None, None, None)
    r_spec = P(None, None)
    axis_names = tuple(data_axes) + ep_axes

    def inner(xl, wr, w1, w3, w2, tr, mask):
        if fsdp:
            w1 = jax.lax.all_gather(w1, "data", axis=3, tiled=True)
            w3 = jax.lax.all_gather(w3, "data", axis=3, tiled=True)
            w2 = jax.lax.all_gather(w2, "data", axis=2, tiled=True)
        b, s, d = xl.shape
        xt = xl.reshape(b * s, d)
        with jax.named_scope("moe.route"):
            logits = router_logits(xt, wr)
            A, gates = top_k_routing(logits, top_k, normalize=norm_topk)
            assignment = None
            if tr is not None:
                tr = traffic_lib.observe(
                    tr, A, placement, _lane_index(dcfg, placement),
                    decay=traffic_decay, axis_names=axis_names,
                    valid=None if mask is None else mask.reshape(b * s))
                if dcfg.engine == "fused_hier" and dcfg.use_balancer:
                    assignment = balancer_lib.algorithm1_groups(
                        traffic_lib.balancer_loads(tr, placement))
        y = fusco.shuffle_ffn(xt, A, gates.astype(xt.dtype), w1[0], w3[0],
                              w2[0], placement, dcfg, assignment)
        return y.reshape(b, s, d), tr

    t_spec = jax.tree.map(lambda l: P(*([None] * l.ndim)), traffic)
    m_spec = None if traffic_mask is None else P(data_axes, ep_axes)
    fn = shard_map(inner, mesh=mesh,
                   in_specs=(x_spec, r_spec, w_spec, w_spec, w2_spec, t_spec,
                             m_spec),
                   out_specs=(x_spec, t_spec), check_vma=False)
    y, new_traffic = fn(x, moe_params["router"], moe_params["w1"],
                        moe_params["w3"], moe_params["w2"], traffic,
                        traffic_mask)
    return y if traffic is None else (y, new_traffic)


def stream_moe_layers(x: jax.Array, moe_params, ln: jax.Array | None, *,
                      mesh, placement: ExpertPlacement, dcfg: DcommConfig,
                      top_k: int, data_axes=("data",), norm_topk: bool = True,
                      stream: bool = True, fsdp: bool = False,
                      interleave: int = 1,
                      traffic: traffic_lib.TrafficState | None = None,
                      traffic_decay: float = 0.99,
                      traffic_mask: jax.Array | None = None):
    """A block of N consecutive MoE layers fused into ONE shard_map island.

    x: (B, S, d) global.  ``moe_params`` holds the block's stacked weights:
    router (N, d, E) replicated, w1/w3 (N, E_lanes, E_local, d, f) and
    w2 (N, E_lanes, E_local, f, d) lane-major over the EP axes.  ``ln`` is
    the (N, d) pre-norm scales (None: no pre-norm).  Each layer applies the
    residual update ``h <- h + moe_l(rms_norm_l(h))`` — norm and residual sit
    inside the island because the cross-layer stream carries layer l's tail
    combine slice into layer l+1's prologue (``fusco.pipe_layer_stream``);
    a per-layer island boundary would reinstate exactly the barrier this
    removes.  With ``stream=False`` (or a non-pipelined engine) the same
    island runs the per-layer-barrier fallback, which is still one island
    per block instead of one per layer.

    ``interleave=K`` splits the island's per-shard batch axis into K
    micro-batch lanes round-robined through one schedule
    (``fusco.interleaved_layer_stream``): lane j+1's router + expert FFN is
    the tail-independent compute that fills lane j's boundary window, which
    the plain K=1 stream leaves empty.  Requires the per-shard batch to be
    divisible by K (lanes are batch chunks, so the token split never cuts a
    sequence).

    ``traffic``: optional BLOCK-stacked ``traffic.TrafficState`` (leading
    ``(N,)`` dim, one slice per layer of this block) threaded through the
    island like in :func:`moe_block` — each layer's routing (all interleave
    lanes) is folded into its slice inside the stream's layer scan, psum'd
    over the island's axes.  Returns ``(y, new_traffic)`` when given.  This
    is what extends the load-adaptive re-layout to the stream family.
    ``traffic_mask``: (B, S) bool validity mask as in :func:`moe_block` —
    the flattened mask rides the observe closure, so pad positions (prefill
    left-pad, interleave pad rows) are excluded from the EMA in every lane
    of every layer of the block.
    """
    ep_axes = dcfg.ep_axis if isinstance(dcfg.ep_axis, (tuple, list)) else (dcfg.ep_axis,)
    ep_axes = tuple(ep_axes)
    x_spec = P(data_axes, ep_axes, None)
    if fsdp:
        # ZeRO-3 expert weights (as in moe_block): stored sharded over the
        # data axis, gathered just-in-time inside the island
        w_spec = P(None, ep_axes, None, None, "data")
        w2_spec = P(None, ep_axes, None, "data", None)
    else:
        w_spec = w2_spec = P(None, ep_axes, None, None, None)
    r_spec = P(None, None, None)
    ln_spec = P(None, None)
    axis_names = tuple(data_axes) + ep_axes

    def inner(xl, wr, w1, w3, w2, lnl, tr, mask):
        if fsdp:
            w1 = jax.lax.all_gather(w1, "data", axis=4, tiled=True)
            w3 = jax.lax.all_gather(w3, "data", axis=4, tiled=True)
            w2 = jax.lax.all_gather(w2, "data", axis=3, tiled=True)
        b, s, d = xl.shape
        if interleave > 1 and b % interleave != 0:
            raise ValueError(
                f"moe stream interleave={interleave} must divide the "
                f"island's per-shard batch {b} (micro-batch lanes are batch "
                "chunks)")
        n = wr.shape[0]
        f = w1.shape[-1]
        observe = None
        if tr is not None:
            my_lane = _lane_index(dcfg, placement)
            # the flat (b*s,) mask is b-major like the stream's token lanes,
            # so it lines up with the lane-concatenated A rows at any K
            valid = mask.reshape(b * s) if mask is not None else None
            observe = lambda st, A: traffic_lib.observe(
                st, A, placement, my_lane, decay=traffic_decay,
                axis_names=axis_names, valid=valid)
        # b-major flattening: rows [j*(b/K)*s, (j+1)*(b/K)*s) are exactly the
        # j-th batch chunk, so the stream's contiguous token lanes ARE the
        # micro-batches of the batch-axis split.
        xt = xl.reshape(b * s, d)
        y = fusco.layer_stream(
            xt, wr, w1.reshape(n, -1, d, f), w3.reshape(n, -1, d, f),
            w2.reshape(n, -1, f, d), placement, dcfg, top_k,
            ln=lnl if ln is not None else None, norm_topk=norm_topk,
            stream=stream, interleave=interleave, traffic=tr, observe=observe)
        if tr is not None:
            y, tr = y
        return y.reshape(b, s, d), tr

    t_spec = jax.tree.map(lambda l: P(*([None] * l.ndim)), traffic)
    m_spec = None if traffic_mask is None else P(data_axes, ep_axes)
    fn = shard_map(inner, mesh=mesh,
                   in_specs=(x_spec, r_spec, w_spec, w_spec, w2_spec, ln_spec,
                             t_spec, m_spec),
                   out_specs=(x_spec, t_spec), check_vma=False)
    lnl = ln if ln is not None else jnp.zeros(
        (moe_params["router"].shape[0], x.shape[-1]), x.dtype)
    y, new_traffic = fn(x, moe_params["router"], moe_params["w1"],
                        moe_params["w3"], moe_params["w2"], lnl, traffic,
                        traffic_mask)
    return y if traffic is None else (y, new_traffic)


def stream_tx_layers(x: jax.Array, moe_params, attn_params, ln1: jax.Array,
                     ln2: jax.Array, *, mesh, placement: ExpertPlacement,
                     dcfg: DcommConfig, top_k: int, positions: jax.Array,
                     n_heads: int, n_kv: int, head_dim: int,
                     rope_theta: float = 1e6, data_axes=("data",),
                     norm_topk: bool = True, stream: bool = True,
                     fsdp: bool = False, interleave: int = 1,
                     traffic: traffic_lib.TrafficState | None = None,
                     traffic_decay: float = 0.99,
                     traffic_mask: jax.Array | None = None,
                     return_kv: bool = False):
    """A block of N attention+MoE transformer layers in ONE shard_map island.

    The ``moe_tx`` island: batch over the data axes, sequence over the EP
    axes — the island OWNS the attention collectives (k/v all-gather over the
    EP axes inside ``fusco.tx_attention``), which is what lets the cross-layer
    stream carry a ``dcomm.PipeTail`` *across an attention block* instead of
    barriering at every layer boundary.  Each layer is the parallel block
    ``h <- h + attn(rms_norm(h, ln1)) + moe(rms_norm(h, ln2))`` evaluated by
    ``fusco.tx_layer_stream``; with the ``fused_pipe`` engine and
    ``stream=True`` layer l's tail combine exchange is in flight while layer
    l's attention (and, with ``interleave=K``, lanes j+1..K-1's whole
    blocks) computes.

    ``moe_params``: block-stacked ``{router (N, d, E), w1/w3
    (N, E_lanes, E_local, d, f), w2 (N, E_lanes, E_local, f, d)}`` lane-major
    over the EP axes; ``attn_params``: ``{wq, wk, wv, wo}`` stacked (N, ...)
    and replicated (the island gathers the full sequence anyway, so TP'ing
    the heads inside it would only re-shard the gather); ``ln1``/``ln2``:
    (N, d) pre-norm scales; ``positions``: (S,) absolute positions.

    ``traffic``/``traffic_decay``/``traffic_mask`` as in
    :func:`stream_moe_layers`.  ``return_kv`` additionally returns the
    block's per-layer RoPE'd full-sequence (k, v) stacks
    ``(N, B, S, n_kv, hd)`` for prefill cache extraction.  Returns
    ``y`` with ``(y, new_traffic)`` / trailing ``kv`` appended per flag.
    """
    ep_axes = dcfg.ep_axis if isinstance(dcfg.ep_axis, (tuple, list)) else (dcfg.ep_axis,)
    ep_axes = tuple(ep_axes)
    x_spec = P(data_axes, ep_axes, None)
    if fsdp:
        w_spec = P(None, ep_axes, None, None, "data")
        w2_spec = P(None, ep_axes, None, "data", None)
    else:
        w_spec = w2_spec = P(None, ep_axes, None, None, None)
    r_spec = P(None, None, None)
    ln_spec = P(None, None)
    a_spec = jax.tree.map(lambda l: P(*([None] * l.ndim)), attn_params)
    axis_names = tuple(data_axes) + ep_axes

    def inner(xl, pos, wr, w1, w3, w2, ap, l1, l2, tr, mask):
        if fsdp:
            w1 = jax.lax.all_gather(w1, "data", axis=4, tiled=True)
            w3 = jax.lax.all_gather(w3, "data", axis=4, tiled=True)
            w2 = jax.lax.all_gather(w2, "data", axis=3, tiled=True)
        b, s, d = xl.shape
        n = wr.shape[0]
        f = w1.shape[-1]
        observe = None
        if tr is not None:
            my_lane = _lane_index(dcfg, placement)
            valid = mask.reshape(b * s) if mask is not None else None
            observe = lambda st, A: traffic_lib.observe(
                st, A, placement, my_lane, decay=traffic_decay,
                axis_names=axis_names, valid=valid)
        params = {"ln1": l1, "ln2": l2, **ap, "router": wr,
                  "w1": w1.reshape(n, -1, d, f),
                  "w3": w3.reshape(n, -1, d, f),
                  "w2": w2.reshape(n, -1, f, d)}
        out = fusco.tx_layer_stream(
            xl, pos, params, placement, dcfg, top_k, n_heads=n_heads,
            n_kv=n_kv, head_dim=head_dim, rope_theta=rope_theta,
            norm_topk=norm_topk, stream=stream, interleave=interleave,
            traffic=tr, observe=observe, return_kv=return_kv)
        if not isinstance(out, tuple):
            out = (out,)
        y, rest = out[0], list(out[1:])
        new_tr = rest.pop(0) if tr is not None else None
        kv = rest.pop(0) if return_kv else None
        return y, new_tr, kv

    t_spec = jax.tree.map(lambda l: P(*([None] * l.ndim)), traffic)
    m_spec = None if traffic_mask is None else P(data_axes, ep_axes)
    kv_spec = (None if not return_kv
               else (P(None, data_axes, None, None, None),) * 2)
    fn = shard_map(inner, mesh=mesh,
                   in_specs=(x_spec, P(None), r_spec, w_spec, w_spec, w2_spec,
                             a_spec, ln_spec, ln_spec, t_spec, m_spec),
                   out_specs=(x_spec, t_spec, kv_spec), check_vma=False)
    y, new_traffic, kv = fn(x, positions, moe_params["router"],
                            moe_params["w1"], moe_params["w3"],
                            moe_params["w2"], attn_params, ln1, ln2, traffic,
                            traffic_mask)
    out = (y,)
    if traffic is not None:
        out += (new_traffic,)
    if return_kv:
        out += (kv,)
    return out[0] if len(out) == 1 else out


def moe_decode_block(x: jax.Array, moe_p, *, mesh, placement: ExpertPlacement,
                     dcfg: DcommConfig, top_k: int, data_axes=("data",),
                     norm_topk: bool = True, fsdp: bool = False):
    """Decode-side MoE: replicated-token EP for single-step decode — every
    lane routes all tokens, computes only its experts' shares, psum over the
    EP axes (a one-token-per-lane all-to-all is degenerate; the FUSCO
    engines live in the prefill path).

    This is the island the continuous-batching serving engine steps once per
    emitted token for the whole slot pool: rows are position-independent here
    (routing reads only the hidden state), so per-slot decode positions need
    no changes on the MoE side — the per-row state lives in the attention
    cache (``layers/attention.KVCache`` with ``(B,)`` lengths).

    Replica choice: decode used to pin replica 0, so a replicated hot
    expert's whole decode load landed on one lane.  It reuses
    ``balanced_replica_choice`` — the same deterministic round-robin on the
    running per-expert count that prefill/training shuffle under (and the
    sender-local analogue of picking the least-EMA-loaded replica, the
    signal the serving engine's ``TrafficState`` tracks) — so decode traffic
    spreads across all lanes hosting a replica.  The choice is replicated
    across lanes (same A everywhere), so exactly one lane still computes
    each (token, k) share and the psum is unchanged.
    """
    ep_axes = (dcfg.ep_axis if isinstance(dcfg.ep_axis, (tuple, list))
               else (dcfg.ep_axis,))
    # decode batches may be smaller than the data axis (long-context b=1)
    dsz = 1
    for ax in data_axes:
        dsz *= dict(mesh.shape)[ax]
    dp = data_axes if x.shape[0] % dsz == 0 and x.shape[0] >= dsz else ()

    def inner(xl, wr, w1, w3, w2):
        # this lane's experts: a view of the layer's slice of the stacked
        # weights, taken outside the block's scope, since the copy it can
        # cost belongs to the layer scan (layers.scan_io in a trace)
        w1, w3, w2 = w1[0], w3[0], w2[0]
        with jax.named_scope("moe.decode"):
            if fsdp:
                # local layout (E_local, d, f_shard)
                w1 = jax.lax.all_gather(w1, "data", axis=2, tiled=True)
                w3 = jax.lax.all_gather(w3, "data", axis=2, tiled=True)
                w2 = jax.lax.all_gather(w2, "data", axis=1, tiled=True)
            b, s, d = xl.shape
            xt = xl.reshape(b * s, d)
            with jax.named_scope("moe.route"):
                logits = router_logits(xt, wr)
                A, gates = top_k_routing(logits, top_k, norm_topk)
                replica = balanced_replica_choice(A, placement)
                lane = placement.lane_of_expert(A, replica)
                eloc = placement.local_expert_index(A, replica)
            my = jax.lax.axis_index(ep_axes[-1])
            if len(ep_axes) == 2:
                my = my + jax.lax.axis_index(ep_axes[0]) * (
                    placement.ep // jax.lax.axis_size(ep_axes[0]))
            # masked dense compute over this lane's experts — every token
            # through every local expert, which is exactly the fused staging
            # kernel's (S=1, E_local, C=T, d) landed layout, all rows live
            with jax.named_scope("moe.expert_ffn"):
                rows = jnp.broadcast_to(xt[None, None],
                                        (1, w1.shape[0]) + xt.shape)
                out_e = kops.fused_swiglu(rows, w1, w3, w2)[0]
            out_e = jnp.moveaxis(out_e, 0, 1)                # (T, E_local, d)
            mask = (lane == my)[..., None] & (
                eloc[..., None] == jnp.arange(placement.experts_per_lane))
            w = (mask * gates[..., None]).sum(axis=1).astype(
                out_e.dtype)                                 # (T, E_local)
            y = jnp.einsum("ted,te->td", out_e, w)
            y = jax.lax.psum(y, ep_axes)
            return y.reshape(b, s, d)

    x_spec = P(dp or None, None, None)
    if fsdp:
        w_spec = P(ep_axes, None, None, "data")
        w2_spec = P(ep_axes, None, "data", None)
    else:
        w_spec = w2_spec = P(ep_axes, None, None, None)
    fn = shard_map(inner, mesh=mesh,
                   in_specs=(x_spec, P(None, None), w_spec, w_spec, w2_spec),
                   out_specs=x_spec, check_vma=False)
    return fn(x, moe_p["router"], moe_p["w1"], moe_p["w3"], moe_p["w2"])


def lane_major_expert_weights(w_all: jax.Array, placement: ExpertPlacement) -> jax.Array:
    """(E, d, f) canonical expert weights -> (ep, E_local, d, f) lane-major
    layout (replicated experts duplicated per hosting lane).  Works for any
    placement — arithmetic or table-driven — via its expert-id table view."""
    from repro.core.relayout import placement_table
    return w_all[jnp.asarray(placement_table(placement))]
