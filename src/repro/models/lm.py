"""Decoder-only LM assembly: dense / MoE / SSM / hybrid / VLM backbones.

One scanned, remat'd layer body per family (XLA compiles a single layer
regardless of depth); FUSCO MoE islands run inside the scan via shard_map.
Training forward, chunked-vocab CE loss, prefill and single-token decode.

Decode note: prefill uses the FUSCO shuffle engines; the per-step decode MoE
uses the replicated-token EP path (mask + psum) because a one-token-per-lane
all-to-all is degenerate — the paper's evaluation targets training and TTFT
(prefill) as well (DESIGN.md §2).
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.dcomm import DcommConfig
from repro.core.routing import ExpertPlacement
from repro.layers import attention as attn_lib
from repro.layers.attention import KVCache, attention_block, cache_update, decode_attention
from repro.layers.common import dense_init, embed_init, rms_norm, apply_rope, apply_mrope
from repro.layers.hybrid import hymba_mixer
from repro.layers.moe import (moe_block, moe_decode_block, stream_moe_layers,
                              stream_tx_layers)
from repro.layers.ssm import SsmState, mamba2_mixer


# ---------------------------------------------------------------------------
# Run-wide model context
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelContext:
    cfg: ArchConfig
    mesh: Any
    multi_pod: bool
    dcfg: DcommConfig | None          # None for non-MoE archs
    placement: ExpertPlacement | None
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True
    loss_chunk: int = 512
    explicit_tp: bool = True
    fsdp_experts: bool = False
    # moe_ffn family: layers per cross-layer stream block (fused_pipe engine
    # overlaps the combine of layer i with the dispatch of layer i+1 inside
    # a block); <=1 keeps per-layer islands.
    moe_stream: int = 0
    # moe_ffn family: token micro-batches interleaved through each stream
    # block (K lanes round-robin through one schedule; lane j+1's compute
    # fills lane j's boundary window).  <=1 = the plain chained stream.
    moe_interleave: int = 1
    # EMA decay of the online traffic statistics (when a TrafficState is
    # threaded through the forward)
    traffic_decay: float = 0.99
    # moe family: per-layer engine override from the comm-path policy
    # (``core/commplan.plan_paths``) — a length-n_layers tuple of engine
    # names; the layer scan splits into contiguous same-engine runs (engine
    # choice is trace-time static).  None = ``dcfg.engine`` everywhere.
    # Stream families (moe_ffn / moe_tx) share one schedule per block and
    # keep the single-engine dcfg.
    engines: tuple | None = None

    def tp_eligible(self):
        """Explicit Megatron-TP blocks need head-divisible archs, plain RoPE,
        and a uniform (non-hybrid) stack."""
        cfg = self.cfg
        return (self.explicit_tp and cfg.n_heads > 0
                and cfg.n_heads % dict(self.mesh.shape)["model"] == 0
                and cfg.mrope_sections is None
                and cfg.family in ("dense", "moe"))

    @property
    def data_axes(self):
        if self.multi_pod and self.cfg.family not in ("moe", "moe_ffn",
                                                      "moe_tx"):
            return ("pod", "data")
        return ("data",)

    @property
    def sp_axes(self):
        if self.multi_pod and self.cfg.family in ("moe", "moe_ffn", "moe_tx"):
            return ("pod", "model")
        return ("model",)

    def act_spec(self):
        return P(self.data_axes, self.sp_axes, None)

    def constrain(self, h):
        return jax.lax.with_sharding_constraint(h, self.act_spec())

    # Megatron-style sub-block layouts: attention runs head-sharded over the
    # full sequence (one AG in, one RS out per block); MLP/SSM intermediates
    # are column-sharded.  Keeps every collective OUT of the flash/SSD loops.
    def q_spec(self):
        return P(self.data_axes, None, "model", None)

    def kv_spec(self):
        return P(self.data_axes, None, None, None)

    def mid_spec(self):
        return P(self.data_axes, None, "model")

    def gathered_spec(self):
        return P(self.data_axes, None, None)

    def gather_seq(self, x):
        """Explicit SP all-gather before a column-parallel projection; its
        transpose (reduce-scatter) is what the backward then emits."""
        return jax.lax.with_sharding_constraint(x, self.gathered_spec())


def make_context(cfg: ArchConfig, mesh, *, multi_pod: bool,
                 engine: str = "fused_flat", capacity_factor: float = 2.0,
                 use_balancer: bool = True, node_size: int | None = None,
                 remat: bool = True, moe_stream: int = 0,
                 moe_interleave: int = 1, pipe_slices: int = 0,
                 traffic_decay: float = 0.99,
                 dedup: bool = False, calibration=None) -> ModelContext:
    placement = dcfg = None
    if cfg.moe is not None:
        axes = dict(mesh.shape)
        ep = axes["model"] * (axes.get("pod", 1) if multi_pod else 1)
        ep_axis = ("pod", "model") if multi_pod else "model"
        ns = node_size or (axes["model"] if multi_pod else max(1, axes["model"] // 4))
        placement = ExpertPlacement(n_experts=cfg.moe.n_experts, ep=ep, node_size=ns)
        dcfg = DcommConfig(engine=engine, ep_axis=ep_axis, node_size=ns,
                           capacity_factor=capacity_factor,
                           use_balancer=use_balancer,
                           pipe_slices=pipe_slices, dedup=dedup)
        if calibration is not None:
            # measured pipe constants (core.calibrate.CalibrationTable)
            # replace the paper's A100/CX-7 defaults; pipesim and commplan
            # both read them off the config
            from repro.core import calibrate as calibrate_lib
            dcfg = calibrate_lib.apply(calibration, dcfg)
    fsdp = False
    if cfg.moe is not None:
        per_lane_gb = (max(1, placement.experts_per_lane) * 3 * cfg.d_model
                       * cfg.moe.d_ff_expert * 2 * cfg.n_layers) / 1e9
        fsdp = per_lane_gb > 4.0       # ZeRO-3 the expert weights when large
    return ModelContext(cfg=cfg, mesh=mesh, multi_pod=multi_pod, dcfg=dcfg,
                        placement=placement, remat=remat, fsdp_experts=fsdp,
                        moe_stream=moe_stream,
                        moe_interleave=max(1, moe_interleave),
                        traffic_decay=traffic_decay)


# ---------------------------------------------------------------------------
# Parameter init (runs under jax.eval_shape for full-size dry-runs)
# ---------------------------------------------------------------------------

def _attn_params(key, cfg: ArchConfig, L: int, dtype):
    d, hd = cfg.d_model, cfg.hd
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (L, d, cfg.n_heads * hd), dtype=dtype),
        "wk": dense_init(ks[1], (L, d, cfg.n_kv_heads * hd), dtype=dtype),
        "wv": dense_init(ks[2], (L, d, cfg.n_kv_heads * hd), dtype=dtype),
        "wo": dense_init(ks[3], (L, cfg.n_heads * hd, d), dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((L, hd), dtype)
        p["k_norm"] = jnp.ones((L, hd), dtype)
    return p


def _mlp_params(key, d, f, L, dtype):
    ks = jax.random.split(key, 3)
    return {"w_gate": dense_init(ks[0], (L, d, f), dtype=dtype),
            "w_up": dense_init(ks[1], (L, d, f), dtype=dtype),
            "w_down": dense_init(ks[2], (L, f, d), dtype=dtype)}


def _moe_params(key, cfg: ArchConfig, placement: ExpertPlacement, L, dtype):
    d, fe = cfg.d_model, cfg.moe.d_ff_expert
    el = placement.experts_per_lane
    ks = jax.random.split(key, 4)
    return {"router": dense_init(ks[0], (L, d, cfg.moe.n_experts), dtype=dtype),
            "w1": dense_init(ks[1], (L, placement.ep, el, d, fe), dtype=dtype),
            "w3": dense_init(ks[2], (L, placement.ep, el, d, fe), dtype=dtype),
            "w2": dense_init(ks[3], (L, placement.ep, el, fe, d), dtype=dtype)}


def _ssm_params(key, cfg: ArchConfig, L, dtype):
    s = cfg.ssm
    d = cfg.d_model
    din = s.expand * d
    h = din // s.head_dim
    conv_dim = din + 2 * s.n_groups * s.d_state
    ks = jax.random.split(key, 5)
    return {
        "in_proj_zx": dense_init(ks[0], (L, d, din + conv_dim), dtype=dtype),
        "in_proj_dt": dense_init(ks[3], (L, d, h), dtype=dtype),
        "conv_w": dense_init(ks[1], (L, s.conv_kernel, conv_dim), scale=0.5, dtype=dtype),
        "dt_bias": jnp.zeros((L, h), dtype),
        "a_log": jnp.zeros((L, h), dtype),           # A = -exp(0) = -1
        "d_skip": jnp.ones((L, h), dtype),
        "norm": jnp.ones((L, din), dtype),
        "out_proj": dense_init(ks[2], (L, din, d), dtype=dtype),
    }


def init_params(cfg: ArchConfig, key, ctx: ModelContext, dtype=jnp.bfloat16):
    L = cfg.n_layers
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    layers: dict = {"ln1": jnp.ones((L, d), dtype)}
    if cfg.family in ("dense", "moe", "vlm", "hybrid", "moe_tx"):
        layers["attn"] = _attn_params(ks[0], cfg, L, dtype)
        layers["ln2"] = jnp.ones((L, d), dtype)
    if cfg.family in ("dense", "vlm", "hybrid"):
        layers["mlp"] = _mlp_params(ks[1], d, cfg.d_ff, L, dtype)
    if cfg.family in ("moe", "moe_ffn", "moe_tx"):
        layers["moe"] = _moe_params(ks[2], cfg, ctx.placement, L, dtype)
    if cfg.family in ("ssm", "hybrid"):
        layers["ssm"] = _ssm_params(ks[3], cfg, L, dtype)
    if cfg.family == "hybrid":
        layers["attn_out_norm"] = jnp.ones((L, d), dtype)
        layers["ssm_out_norm"] = jnp.ones((L, d), dtype)
    params = {
        "embed": embed_init(ks[4], cfg.vocab, d, dtype),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": dense_init(ks[5], (d, cfg.vocab), dtype=dtype),
    }
    return params


def _ssm_args(cfg: ArchConfig):
    s = cfg.ssm
    din = s.expand * cfg.d_model
    return dict(d_inner=din, n_heads=din // s.head_dim, head_dim=s.head_dim,
                d_state=s.d_state, n_groups=s.n_groups, chunk=s.chunk)


def _is_global_flags(cfg: ArchConfig):
    return jnp.array([i in cfg.global_layers for i in range(cfg.n_layers)],
                     jnp.bool_)


# ---------------------------------------------------------------------------
# Training / prefill forward
# ---------------------------------------------------------------------------


def _layer_runs(cfg: ArchConfig):
    """Contiguous runs of (start, end, is_global) for segmented layer scans.
    Splitting the scan at global-attention layers lets each segment compile
    with a STATIC window (single attention branch + block-skipping flash)."""
    flags = [i in cfg.global_layers for i in range(cfg.n_layers)]
    runs = []
    s = 0
    for i in range(1, cfg.n_layers + 1):
        if i == cfg.n_layers or flags[i] != flags[s]:
            runs.append((s, i, flags[s]))
            s = i
    return runs


def _engine_runs(engines):
    """Contiguous (start, end, engine) runs of a per-layer engine list —
    the comm-path policy's analogue of :func:`_layer_runs`."""
    runs = []
    s = 0
    n = len(engines)
    for i in range(1, n + 1):
        if i == n or engines[i] != engines[s]:
            runs.append((s, i, engines[s]))
            s = i
    return runs


def _moe_scope(cfg: ArchConfig, name: str):
    """Named scope for the MoE branch's norm or residual add (nothing for
    a dense MLP), so a layer body holds no unnamed work of its own."""
    if cfg.family == "moe":
        return jax.named_scope(name)
    return contextlib.nullcontext()


def _scan_layers(layer_fn, h, layers, cfg: ArchConfig, remat: bool):
    """Scan over layers; hybrid archs run one scan per global/SWA segment.
    layer_fn(h, lp, is_global) -> (h, ys)."""
    if cfg.family == "hybrid" and cfg.global_layers:
        ys_all = []
        for a, b, gflag in _layer_runs(cfg):
            seg = jax.tree.map(lambda x: x[a:b], layers)
            body = partial(layer_fn, is_global=gflag)
            body = jax.checkpoint(body) if remat else body
            with jax.named_scope("layers"):
                h, ys = jax.lax.scan(body, h, seg)
            ys_all.append(ys)
        if ys_all and ys_all[0] is not None and jax.tree.leaves(ys_all[0]):
            ys = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *ys_all)
        else:
            ys = None
        return h, ys
    body = partial(layer_fn, is_global=False)
    body = jax.checkpoint(body) if remat else body
    with jax.named_scope("layers"):
        return jax.lax.scan(body, h, layers)


def _tx_stack(params, h, positions, ctx: ModelContext, traffic=None,
              traffic_mask=None, return_kv=False):
    """moe_tx stack: layers grouped into attention-separated stream blocks —
    one shard_map island per block (``layers/moe.stream_tx_layers``), the
    island owning both the FUSCO shuffle and the attention collectives, so
    inside a block layer l's MoE tail combine stays in flight across the
    attention block instead of barriering at the layer boundary.  Returns
    ``(final-normed h, new_traffic | None, kv | None)`` — ``kv`` is the
    per-layer RoPE'd full-sequence cache stack ``{"k","v"}: (L, B, S, Hkv,
    hd)`` when ``return_kv`` (prefill)."""
    cfg = ctx.cfg
    cd = ctx.compute_dtype
    L = cfg.n_layers
    blk = max(1, ctx.moe_stream)
    if L % blk != 0:
        raise ValueError(
            f"moe_stream={ctx.moe_stream} must divide n_layers={L} "
            "(every stream block needs the same static slice geometry)")
    reblock = lambda a: a.reshape((L // blk, blk) + a.shape[1:])
    blocks = jax.tree.map(reblock, params["layers"])

    def block_fn(h, bp):
        tr = None
        if traffic is not None:
            bp, tr = bp
        bp = jax.tree.map(lambda x: x.astype(cd)
                          if x.dtype in (jnp.float32, jnp.bfloat16) else x,
                          bp)
        out = stream_tx_layers(
            h, bp["moe"], bp["attn"], bp["ln1"], bp["ln2"], mesh=ctx.mesh,
            placement=ctx.placement, dcfg=ctx.dcfg, top_k=cfg.moe.top_k,
            positions=positions, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.hd, rope_theta=cfg.rope_theta,
            data_axes=ctx.data_axes, norm_topk=cfg.moe.norm_topk,
            fsdp=ctx.fsdp_experts, interleave=ctx.moe_interleave,
            traffic=tr, traffic_decay=ctx.traffic_decay,
            traffic_mask=traffic_mask, return_kv=return_kv)
        if not isinstance(out, tuple):
            out = (out,)
        h, rest = out[0], list(out[1:])
        if traffic is not None:
            tr = rest.pop(0)
        kv = rest.pop(0) if return_kv else None
        return ctx.constrain(h), (tr, kv)

    body = jax.checkpoint(block_fn) if ctx.remat else block_fn
    xs = blocks if traffic is None else (blocks, jax.tree.map(reblock, traffic))
    with jax.named_scope("layers"):
        h, (new_traffic, kv) = jax.lax.scan(body, h, xs)
    h = rms_norm(h, params["final_norm"].astype(cd))
    unblock = lambda a: a.reshape((L,) + a.shape[2:])
    if traffic is not None:
        new_traffic = jax.tree.map(unblock, new_traffic)
    if return_kv:
        kv = {"k": unblock(kv[0]), "v": unblock(kv[1])}
    return h, new_traffic, kv


def forward_hidden(params, inputs, positions, ctx: ModelContext,
                   traffic=None, traffic_mask=None):
    """inputs: (B, S) int tokens, or (B, S, d) embeddings (VLM/audio stubs).
    Returns final-norm'd hidden states (B, S, d) in compute dtype.

    ``traffic``: optional per-layer stacked ``traffic.TrafficState`` (leading
    ``(L,)`` dim, like stacked layer params) threaded through the MoE islands
    — each layer's slice rides the layer scan as xs and comes back updated as
    ys, exactly like RNG state would.  Returns ``(h, new_traffic)`` when
    given.  Supported for the ``moe`` family (per-layer islands) and the
    ``moe_ffn``/``moe_tx`` families (slices regrouped per stream block,
    observed inside the block island's layer-stream scan).
    ``traffic_mask``: optional (B, S) bool validity mask — pad positions are
    excluded from the traffic counts (see ``traffic.observe``)."""
    cfg = ctx.cfg
    cd = ctx.compute_dtype
    if traffic is not None and cfg.family not in ("moe", "moe_ffn", "moe_tx"):
        raise ValueError(
            f"traffic stats are threaded per-layer through the MoE islands; "
            f"family {cfg.family!r} is not supported (moe / moe_ffn / moe_tx "
            "only)")
    if inputs.ndim == 2:
        h = params["embed"].astype(cd)[inputs]
    else:
        h = inputs.astype(cd)
    h = ctx.constrain(h)

    ssm_args = _ssm_args(cfg) if cfg.ssm else None

    if cfg.family == "moe_tx":
        # attention-separated MoE transformer: blocks of parallel attention+
        # MoE layers fused into one island each — the MoE tail combine of
        # layer l rides across layer l's attention block (fused_pipe engine;
        # other engines run the same island with per-layer barriers).
        h, new_traffic, _ = _tx_stack(params, h, positions, ctx,
                                      traffic=traffic,
                                      traffic_mask=traffic_mask)
        return h if traffic is None else (h, new_traffic)

    if cfg.family == "moe_ffn":
        # pure MoE-FFN stack: layers grouped into cross-layer stream blocks —
        # one shard_map island per block instead of one per layer, so inside
        # a block the combine of layer i overlaps the dispatch of layer i+1
        # (fused_pipe engine; other engines run the same island per-layer).
        L = cfg.n_layers
        blk = max(1, ctx.moe_stream)
        if L % blk != 0:
            raise ValueError(
                f"moe_stream={ctx.moe_stream} must divide n_layers={L} "
                "(every stream block needs the same static slice geometry)")
        reblock = lambda a: a.reshape((L // blk, blk) + a.shape[1:])
        blocks = jax.tree.map(reblock, params["layers"])

        def block_fn(h, bp):
            tr = None
            if traffic is not None:
                bp, tr = bp
            bp = jax.tree.map(lambda x: x.astype(cd)
                              if x.dtype in (jnp.float32, jnp.bfloat16) else x,
                              bp)
            h = stream_moe_layers(
                h, bp["moe"], bp["ln1"], mesh=ctx.mesh,
                placement=ctx.placement, dcfg=ctx.dcfg, top_k=cfg.moe.top_k,
                data_axes=ctx.data_axes, norm_topk=cfg.moe.norm_topk,
                fsdp=ctx.fsdp_experts, interleave=ctx.moe_interleave,
                traffic=tr, traffic_decay=ctx.traffic_decay,
                traffic_mask=traffic_mask)
            if tr is not None:
                h, tr = h
            return ctx.constrain(h), tr

        body = jax.checkpoint(block_fn) if ctx.remat else block_fn
        xs = blocks if traffic is None else (
            blocks, jax.tree.map(reblock, traffic))
        with jax.named_scope("layers"):
            h, new_traffic = jax.lax.scan(body, h, xs)
        h = rms_norm(h, params["final_norm"].astype(cd))
        if traffic is None:
            return h
        # un-block the per-layer traffic slices back to a flat (L,) stack
        return h, jax.tree.map(
            lambda a: a.reshape((L,) + a.shape[2:]), new_traffic)

    def layer_fn(h, lp, is_global=False, dcfg=None):
        dcfg = ctx.dcfg if dcfg is None else dcfg
        tr = None
        if traffic is not None:
            lp, tr = lp
        lp = jax.tree.map(lambda x: x.astype(cd)
                          if x.dtype in (jnp.float32, jnp.bfloat16) else x, lp)
        if cfg.family in ("dense", "moe", "vlm", "hybrid"):
            use_tp = ctx.tp_eligible()
            with jax.named_scope("attention"):
                if cfg.family == "hybrid":
                    x = ctx.gather_seq(rms_norm(h, lp["ln1"]))
                    mix, _, _ = hymba_mixer(
                        x, lp, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                        head_dim=cfg.hd, rope_theta=cfg.rope_theta,
                        positions=positions, window=cfg.window,
                        is_global=is_global, ssm_args=ssm_args,
                        shard_ctx=(ctx.mesh, ctx.data_axes, "model"),
                        mid_spec=ctx.mid_spec())
                elif use_tp:
                    from repro.parallel.tp_blocks import megatron_attention
                    x = rms_norm(h, lp["ln1"])     # stays sequence-sharded
                    mix = megatron_attention(
                        x, lp["attn"], mesh=ctx.mesh, data_axes=ctx.data_axes,
                        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                        head_dim=cfg.hd, rope_theta=cfg.rope_theta,
                        positions=positions, causal=True, window=cfg.window,
                        qk_norm=cfg.qk_norm)
                else:
                    x = ctx.gather_seq(rms_norm(h, lp["ln1"]))
                    mix = attention_block(
                        x, lp["attn"], n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                        head_dim=cfg.hd, rope_theta=cfg.rope_theta,
                        positions=positions, causal=True, window=cfg.window,
                        qk_norm=cfg.qk_norm, mrope_sections=cfg.mrope_sections,
                        shard_ctx=(ctx.mesh, ctx.data_axes, "model"))
                h = ctx.constrain(h + mix)
            if cfg.family == "moe":
                with jax.named_scope("moe.route"):
                    x = rms_norm(h, lp["ln2"])  # island is sequence-sharded
                y = moe_block(x, lp["moe"], mesh=ctx.mesh,
                              placement=ctx.placement, dcfg=dcfg,
                              top_k=cfg.moe.top_k, data_axes=ctx.data_axes,
                              norm_topk=cfg.moe.norm_topk,
                              fsdp=ctx.fsdp_experts, traffic=tr,
                              traffic_decay=ctx.traffic_decay,
                              traffic_mask=traffic_mask)
                if tr is not None:
                    y, tr = y
            elif use_tp:
                from repro.parallel.tp_blocks import megatron_mlp
                x = rms_norm(h, lp["ln2"])
                y = megatron_mlp(x, lp["mlp"], mesh=ctx.mesh,
                                 data_axes=ctx.data_axes)
            else:
                x = ctx.gather_seq(rms_norm(h, lp["ln2"]))
                u = jax.lax.with_sharding_constraint(
                    x @ lp["mlp"]["w_gate"], ctx.mid_spec())
                w = jax.lax.with_sharding_constraint(
                    x @ lp["mlp"]["w_up"], ctx.mid_spec())
                y = (jax.nn.silu(u) * w) @ lp["mlp"]["w_down"]
            with _moe_scope(cfg, "moe.combine"):
                h = ctx.constrain(h + y)
        elif cfg.family == "ssm":
            x = ctx.gather_seq(rms_norm(h, lp["ln1"]))
            y, _ = mamba2_mixer(x, lp["ssm"], mid_spec=ctx.mid_spec(),
                                **ssm_args)
            h = ctx.constrain(h + y)
        else:
            raise ValueError(cfg.family)
        return h, tr

    xs = params["layers"] if traffic is None else (params["layers"], traffic)
    if cfg.family == "moe" and ctx.engines is not None:
        # comm-path policy: per-layer engine choice is trace-time static, so
        # the layer scan splits into contiguous same-engine runs — the same
        # segmentation trick the hybrid family uses for global/SWA windows.
        if len(ctx.engines) != cfg.n_layers:
            raise ValueError(
                f"ctx.engines has {len(ctx.engines)} entries for "
                f"{cfg.n_layers} layers")
        ys_all = []
        for a, b, eng in _engine_runs(ctx.engines):
            seg = jax.tree.map(lambda x: x[a:b], xs)
            dcfg_run = dataclasses.replace(
                ctx.dcfg, engine=eng,
                dedup=ctx.dcfg.dedup and eng == "fused_flat")
            body = partial(layer_fn, is_global=False, dcfg=dcfg_run)
            body = jax.checkpoint(body) if ctx.remat else body
            with jax.named_scope("layers"):
                h, ys = jax.lax.scan(body, h, seg)
            ys_all.append(ys)
        if ys_all and ys_all[0] is not None and jax.tree.leaves(ys_all[0]):
            new_traffic = jax.tree.map(
                lambda *x: jnp.concatenate(x, 0), *ys_all)
        else:
            new_traffic = None
    else:
        h, new_traffic = _scan_layers(layer_fn, h, xs, cfg, ctx.remat)
    h = rms_norm(h, params["final_norm"].astype(cd))
    return h if traffic is None else (h, new_traffic)


def lm_loss(params, batch, ctx: ModelContext, traffic=None):
    """Next-token CE, chunked over the sequence so (B, Sc, V) logits never
    exceed the activation budget.  Returns (loss, metrics); with ``traffic``
    the updated per-layer traffic state rides along as ``metrics["traffic"]``
    (an aux output — counts derive from the int routing matrix, so no
    gradient flows through it)."""
    cfg = ctx.cfg
    inputs = batch.get("embeds", batch.get("tokens"))
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.arange(inputs.shape[1])
    h = forward_hidden(params, inputs, positions, ctx, traffic=traffic)
    new_traffic = None
    if traffic is not None:
        h, new_traffic = h
    labels = batch["labels"]                     # (B, S) — already shifted
    head = params["lm_head"].astype(ctx.compute_dtype)

    b, s, d = h.shape
    c = min(ctx.loss_chunk, s)
    nc = s // c
    hc = jnp.moveaxis(h.reshape(b, nc, c, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, nc, c), 1, 0)

    def chunk(carry, xs):
        hx, lx = xs                               # (B, c, d), (B, c)
        logits = (hx @ head).astype(jnp.float32)  # (B, c, V)
        logits = jax.lax.with_sharding_constraint(
            logits, P(ctx.data_axes, None, "model"))
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lx[..., None], axis=-1)[..., 0]
        valid = lx >= 0
        loss = jnp.where(valid, logz - gold, 0.0).sum()
        return carry + jnp.stack([loss, valid.sum().astype(jnp.float32)]), None

    tot, _ = jax.lax.scan(jax.checkpoint(chunk), jnp.zeros((2,)), (hc, lc))
    loss = tot[0] / jnp.maximum(tot[1], 1.0)
    metrics = {"loss": loss, "tokens": tot[1]}
    if new_traffic is not None:
        metrics["traffic"] = new_traffic
    return loss, metrics


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------

class DecodeState(NamedTuple):
    kv: Any            # stacked (L, ...) KVCache arrays or None
    ssm: Any           # stacked SsmState arrays or None
    length: jax.Array  # () int32 — or (B,) int32 per-row positions when the
                       # state is a continuous-batching slot pool (each slot
                       # decodes at its own position; free slots sit at 0)


def _kv_capacity(cfg: ArchConfig, max_len: int) -> int:
    # hybrid archs with global layers need full history in those layers; we
    # allocate full caches for all layers then (uniform scan stack).
    if cfg.family == "hybrid" and cfg.global_layers:
        return max_len
    return min(max_len, cfg.window) if cfg.window else max_len


def init_decode_state(cfg: ArchConfig, batch: int, max_len: int, dtype,
                      ctx: ModelContext, per_slot: bool = False) -> DecodeState:
    """Zeroed decode state; ``per_slot=True`` makes ``length`` per-row
    ((batch,) int32) — the continuous-batching slot pool, where each row is
    an independent request at its own position."""
    L = cfg.n_layers
    kv = ssm = None
    if cfg.family in ("dense", "moe", "moe_tx", "vlm", "hybrid", "encdec"):
        c = _kv_capacity(cfg, max_len)
        kv = {"k": jnp.zeros((L, batch, c, cfg.n_kv_heads, cfg.hd), dtype),
              "v": jnp.zeros((L, batch, c, cfg.n_kv_heads, cfg.hd), dtype)}
    if cfg.family in ("ssm", "hybrid"):
        s = cfg.ssm
        din = s.expand * cfg.d_model
        h = din // s.head_dim
        conv_dim = din + 2 * s.n_groups * s.d_state
        ssm = {"state": jnp.zeros((L, batch, h, s.head_dim, s.d_state), dtype),
               "conv": jnp.zeros((L, batch, s.conv_kernel - 1, conv_dim), dtype)}
    return DecodeState(kv, ssm,
                       jnp.zeros((batch,) if per_slot else (), jnp.int32))


def _moe_decode_block(x, moe_p, ctx: ModelContext):
    """Decode-side MoE island — see ``layers/moe.moe_decode_block``."""
    cfg = ctx.cfg
    return moe_decode_block(x, moe_p, mesh=ctx.mesh, placement=ctx.placement,
                            dcfg=ctx.dcfg, top_k=cfg.moe.top_k,
                            data_axes=ctx.data_axes,
                            norm_topk=cfg.moe.norm_topk,
                            fsdp=ctx.fsdp_experts)


def decode_step(params, state: DecodeState, inputs, ctx: ModelContext,
                max_len: int):
    """One-token decode.  inputs: (B,) int32 tokens or (B, 1, d) embeddings.
    Returns (logits (B, V), new DecodeState).

    ``state.length`` may be a scalar (classic lock-step batch: every row at
    the same position) or (B,) per-row positions (continuous-batching slot
    pool: each row RoPE-rotates, cache-writes and masks at its own position
    — what lets a freshly prefilled request decode next to slots mid-way
    through theirs)."""
    cfg = ctx.cfg
    cd = ctx.compute_dtype
    if inputs.ndim == 1:
        h = params["embed"].astype(cd)[inputs][:, None, :]
    else:
        h = inputs.astype(cd)
    b = h.shape[0]
    pos = state.length
    if pos.ndim == 1:
        positions = pos[:, None].astype(jnp.int32)       # (B, 1) per-row
    else:
        positions = pos[None].astype(jnp.int32)          # (1,)
    if cfg.mrope_sections:
        positions = jnp.broadcast_to(positions[None],
                                     (3,) + positions.shape)
    ssm_args = _ssm_args(cfg) if cfg.ssm else None
    flags = _is_global_flags(cfg)

    def layer_fn(h, xs):
        lp, is_global, kv_l, ssm_l = xs
        lp = jax.tree.map(lambda x: x.astype(cd)
                          if x.dtype in (jnp.float32, jnp.bfloat16) else x, lp)
        new_kv, new_ssm = kv_l, ssm_l
        if cfg.family in ("dense", "moe", "vlm"):
            with jax.named_scope("attention"):
                x = rms_norm(h, lp["ln1"])
                q, k, v = attn_lib.gqa_project(
                    x, lp["attn"]["wq"], lp["attn"]["wk"], lp["attn"]["wv"],
                    cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                    lp["attn"].get("q_norm") if cfg.qk_norm else None,
                    lp["attn"].get("k_norm") if cfg.qk_norm else None)
                if cfg.mrope_sections:
                    q = apply_mrope(q, positions, cfg.mrope_sections,
                                    cfg.rope_theta)
                    k = apply_mrope(k, positions, cfg.mrope_sections,
                                    cfg.rope_theta)
                else:
                    q = apply_rope(q, positions, cfg.rope_theta)
                    k = apply_rope(k, positions, cfg.rope_theta)
                cache = KVCache(kv_l["k"], kv_l["v"], pos, max_len)
                cache = cache_update(cache, k, v)
                a = decode_attention(q, cache)
                new_kv = {"k": cache.k, "v": cache.v}
                mix = a.reshape(b, 1, cfg.n_heads * cfg.hd) @ lp["attn"]["wo"]
                h = h + mix
            if cfg.family == "moe":
                # the block opens moe.decode itself, after its view of the
                # layer's expert weights (see layers/moe.moe_decode_block)
                with jax.named_scope("moe.decode"):
                    x = rms_norm(h, lp["ln2"])
                y = _moe_decode_block(x, lp["moe"], ctx)
                with jax.named_scope("moe.decode"):
                    h = h + y
            else:
                x = rms_norm(h, lp["ln2"])
                y = jax.nn.silu(x @ lp["mlp"]["w_gate"]) * (x @ lp["mlp"]["w_up"])
                y = y @ lp["mlp"]["w_down"]
                h = h + y
        elif cfg.family == "moe_tx":
            # parallel block: attention AND the MoE branch both read the
            # block input h (what makes the attention tail-independent in
            # the streamed prefill — decode must match that function)
            x = rms_norm(h, lp["ln1"])
            q, k, v = attn_lib.gqa_project(
                x, lp["attn"]["wq"], lp["attn"]["wk"], lp["attn"]["wv"],
                cfg.n_heads, cfg.n_kv_heads, cfg.hd)
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
            cache = KVCache(kv_l["k"], kv_l["v"], pos, max_len)
            cache = cache_update(cache, k, v)
            a = decode_attention(q, cache)
            new_kv = {"k": cache.k, "v": cache.v}
            mix = a.reshape(b, 1, cfg.n_heads * cfg.hd) @ lp["attn"]["wo"]
            y = _moe_decode_block(rms_norm(h, lp["ln2"]), lp["moe"], ctx)
            h = h + mix + y
        elif cfg.family == "moe_ffn":
            x = rms_norm(h, lp["ln1"])
            h = h + _moe_decode_block(x, lp["moe"], ctx)
        elif cfg.family == "ssm":
            x = rms_norm(h, lp["ln1"])
            st = SsmState(ssm_l["state"], ssm_l["conv"])
            y, st2 = mamba2_mixer(x, lp["ssm"], state=st, single_step=True,
                                  **ssm_args)
            new_ssm = {"state": st2.ssd, "conv": st2.conv}
            h = h + y
        elif cfg.family == "hybrid":
            x = rms_norm(h, lp["ln1"])
            cache = KVCache(kv_l["k"], kv_l["v"], pos, max_len)
            st = SsmState(ssm_l["state"], ssm_l["conv"])
            mix, cache2, st2 = hymba_mixer(
                x, lp, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                head_dim=cfg.hd, rope_theta=cfg.rope_theta, positions=positions,
                window=cfg.window, is_global=is_global, ssm_args=ssm_args,
                attn_cache=cache, ssm_state=st, single_step=True)
            new_kv = {"k": cache2.k, "v": cache2.v}
            new_ssm = {"state": st2.ssd, "conv": st2.conv}
            h = h + mix
            x = rms_norm(h, lp["ln2"])
            y = jax.nn.silu(x @ lp["mlp"]["w_gate"]) * (x @ lp["mlp"]["w_up"])
            y = y @ lp["mlp"]["w_down"]
            h = h + y
        return h, (new_kv, new_ssm)

    xs = (params["layers"], flags,
          state.kv if state.kv is not None else
          jax.tree.map(lambda _: jnp.zeros((cfg.n_layers,)), flags),
          state.ssm if state.ssm is not None else
          jax.tree.map(lambda _: jnp.zeros((cfg.n_layers,)), flags))
    with jax.named_scope("layers"):
        h, (new_kv, new_ssm) = jax.lax.scan(layer_fn, h, xs)
    with jax.named_scope("lm_head"):
        h = rms_norm(h, params["final_norm"].astype(cd))
        logits = (h[:, 0] @ params["lm_head"].astype(cd)).astype(
            jnp.float32)
    return logits, DecodeState(
        new_kv if state.kv is not None else None,
        new_ssm if state.ssm is not None else None,
        state.length + 1)


def prefill(params, inputs, positions, ctx: ModelContext, max_len: int,
            traffic=None, traffic_mask=None):
    """Run the full-sequence forward and materialise decode state.

    Implemented as forward_hidden + per-layer cache extraction for attention
    archs (recompute-free: k/v are emitted as scan ys).  ``traffic`` (moe
    families): per-layer stacked traffic state threaded through the MoE
    islands; returns ``(logits, state, new_traffic)`` when given — this is
    what lets the serving engine report per-wave expert-load stats.
    ``traffic_mask``: (B, S) bool — True for real tokens; serving passes it
    so left-pad slots and interleave pad rows don't count toward the EMA."""
    cfg = ctx.cfg
    cd = ctx.compute_dtype
    if traffic is not None and cfg.family not in ("moe", "moe_ffn", "moe_tx"):
        raise ValueError(
            f"traffic stats in prefill are supported for the "
            f"moe/moe_ffn/moe_tx families only, got {cfg.family!r}")
    if cfg.family == "moe_tx":
        # stream blocks + cache extraction: the islands return their layers'
        # RoPE'd full-sequence k/v stacks (identical on every EP lane)
        if inputs.ndim == 2:
            h = params["embed"].astype(cd)[inputs]
        else:
            h = inputs.astype(cd)
        h = ctx.constrain(h)
        s = h.shape[1]
        h, new_traffic, kv = _tx_stack(params, h, positions, ctx,
                                       traffic=traffic,
                                       traffic_mask=traffic_mask,
                                       return_kv=True)
        logits = (h[:, -1] @ params["lm_head"].astype(cd)).astype(jnp.float32)
        cap = _kv_capacity(cfg, max_len)
        k, v = kv["k"], kv["v"]                 # (L, B, S, Hkv, hd)
        if s >= cap:
            ks_ = jnp.roll(k[:, :, -cap:], s % cap, axis=2)
            vs_ = jnp.roll(v[:, :, -cap:], s % cap, axis=2)
        else:
            pad = ((0, 0), (0, 0), (0, cap - s), (0, 0), (0, 0))
            ks_, vs_ = jnp.pad(k, pad), jnp.pad(v, pad)
        state = DecodeState({"k": ks_, "v": vs_}, None,
                            jnp.array(s, jnp.int32))
        if traffic is not None:
            return logits, state, new_traffic
        return logits, state
    if cfg.family == "moe_ffn":
        # stateless stack: prefill is just the forward (stream blocks incl.)
        h = forward_hidden(params, inputs, positions, ctx, traffic=traffic,
                           traffic_mask=traffic_mask)
        new_traffic = None
        if traffic is not None:
            h, new_traffic = h
        logits = (h[:, -1] @ params["lm_head"].astype(cd)).astype(jnp.float32)
        state = DecodeState(None, None, jnp.array(h.shape[1], jnp.int32))
        if traffic is not None:
            return logits, state, new_traffic
        return logits, state
    if inputs.ndim == 2:
        h = params["embed"].astype(cd)[inputs]
    else:
        h = inputs.astype(cd)
    h = ctx.constrain(h)
    b, s, _ = h.shape
    ssm_args = _ssm_args(cfg) if cfg.ssm else None
    flags = _is_global_flags(cfg)
    cap = _kv_capacity(cfg, max_len)

    def layer_fn(h, lp, is_global=False):
        tr = None
        if traffic is not None:
            lp, tr = lp
        lp = jax.tree.map(lambda x: x.astype(cd)
                          if x.dtype in (jnp.float32, jnp.bfloat16) else x, lp)
        kv_out = ssm_out = None
        # no explicit-TP blocks here: they win in training (collective-bound)
        # but measured ~15% worse on the memory-bound prefill
        if cfg.family in ("dense", "moe", "vlm", "hybrid"):
            with jax.named_scope("attention"):
                x = ctx.gather_seq(rms_norm(h, lp["ln1"]))
                if cfg.family == "hybrid":
                    mix, _, st2 = hymba_mixer(
                        x, lp, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                        head_dim=cfg.hd, rope_theta=cfg.rope_theta,
                        positions=positions, window=cfg.window,
                        is_global=is_global, ssm_args=ssm_args,
                        shard_ctx=(ctx.mesh, ctx.data_axes, "model"),
                        mid_spec=ctx.mid_spec())
                    ssm_out = {"state": st2.ssd, "conv": st2.conv}
                    # caches for attention branch recomputed below
                    q, k, v = attn_lib.gqa_project(
                        x, lp["attn"]["wq"], lp["attn"]["wk"], lp["attn"]["wv"],
                        cfg.n_heads, cfg.n_kv_heads, cfg.hd)
                else:
                    mix = attention_block(
                        x, lp["attn"], n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                        head_dim=cfg.hd, rope_theta=cfg.rope_theta,
                        positions=positions, causal=True, window=cfg.window,
                        qk_norm=cfg.qk_norm, mrope_sections=cfg.mrope_sections,
                        shard_ctx=(ctx.mesh, ctx.data_axes, "model"))
                    q, k, v = attn_lib.gqa_project(
                        x, lp["attn"]["wq"], lp["attn"]["wk"], lp["attn"]["wv"],
                        cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                        lp["attn"].get("q_norm") if cfg.qk_norm else None,
                        lp["attn"].get("k_norm") if cfg.qk_norm else None)
                if cfg.mrope_sections:
                    k = apply_mrope(k, positions, cfg.mrope_sections,
                                    cfg.rope_theta)
                else:
                    k = apply_rope(k, positions, cfg.rope_theta)
                # last `cap` positions fill the ring cache; position p -> slot
                # p % cap, so the packed window is rolled by s % cap.
                if s >= cap:
                    ks_ = jnp.roll(k[:, -cap:], s % cap, axis=1)
                    vs_ = jnp.roll(v[:, -cap:], s % cap, axis=1)
                else:
                    pad = ((0, 0), (0, cap - s), (0, 0), (0, 0))
                    ks_, vs_ = jnp.pad(k, pad), jnp.pad(v, pad)
                kv_out = {"k": ks_, "v": vs_}
                h = ctx.constrain(h + mix)
            with _moe_scope(cfg, "moe.route"):
                x = ctx.gather_seq(rms_norm(h, lp["ln2"]))
            if cfg.family == "moe":
                y = moe_block(x, lp["moe"], mesh=ctx.mesh, placement=ctx.placement,
                              dcfg=ctx.dcfg, top_k=cfg.moe.top_k,
                              data_axes=ctx.data_axes, norm_topk=cfg.moe.norm_topk,
                              traffic=tr, traffic_decay=ctx.traffic_decay,
                              traffic_mask=traffic_mask)
                if tr is not None:
                    y, tr = y
            else:
                u = jax.lax.with_sharding_constraint(
                    x @ lp["mlp"]["w_gate"], ctx.mid_spec())
                w = jax.lax.with_sharding_constraint(
                    x @ lp["mlp"]["w_up"], ctx.mid_spec())
                y = (jax.nn.silu(u) * w) @ lp["mlp"]["w_down"]
            with _moe_scope(cfg, "moe.combine"):
                h = ctx.constrain(h + y)
        elif cfg.family == "ssm":
            x = ctx.gather_seq(rms_norm(h, lp["ln1"]))
            y, st2 = mamba2_mixer(x, lp["ssm"], mid_spec=ctx.mid_spec(),
                                    **ssm_args)
            ssm_out = {"state": st2.ssd, "conv": st2.conv}
            h = ctx.constrain(h + y)
        dummy = jnp.zeros((), jnp.int32)
        ys = (kv_out if kv_out is not None else dummy,
              ssm_out if ssm_out is not None else dummy)
        if traffic is not None:
            ys = ys + (tr,)
        return h, ys

    xs = params["layers"] if traffic is None else (params["layers"], traffic)
    h, ys = _scan_layers(layer_fn, h, xs, cfg, ctx.remat)
    kv, ssm = ys[0], ys[1]
    with jax.named_scope("lm_head"):
        h = rms_norm(h, params["final_norm"].astype(cd))
        logits = (h[:, -1] @ params["lm_head"].astype(cd)).astype(
            jnp.float32)
    has_kv = cfg.family in ("dense", "moe", "vlm", "hybrid")
    has_ssm = cfg.family in ("ssm", "hybrid")
    state = DecodeState(kv if has_kv else None, ssm if has_ssm else None,
                        jnp.array(s, jnp.int32))
    if traffic is not None:
        return logits, state, ys[2]
    return logits, state
