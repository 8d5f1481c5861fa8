"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Builds the mesh from the available devices (production meshes are exercised
via dryrun.py), wires the FUSCO engine per config, and runs the
fault-tolerant loop with checkpointing and the deterministic data stream.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.checkpoint import checkpointer
from repro.configs import get_arch
from repro.core import commplan, relayout, traffic as traffic_lib
from repro.data.pipeline import ShardedLoader, SyntheticLM, ZipfNgramLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch import steps as steps_mod
from repro.launch.steps import batch_specs, make_train_step
from repro.models import zoo
from repro.models.lm import make_context
from repro.optim import adamw
from repro.parallel import sharding as sh
from repro.runtime.fault_tolerance import RunConfig, RunState, run_training


def _migrate_moe_tree(tree, old_placement, new_placement):
    """Re-layout the lane-major expert leaves of a params-shaped tree
    (``layers/moe/{w1,w3,w2}``, each ``(L, ep, e_local, ...)``) onto a new
    placement.  Everything else (router, dense layers) is placement-invariant."""
    moe = tree["layers"]["moe"]
    out = dict(moe)
    for name in ("w1", "w3", "w2"):
        out[name] = relayout.migrate_lane_major(
            moe[name], old_placement, new_placement, lane_axis=1)
    tree = dict(tree)
    tree["layers"] = dict(tree["layers"])
    tree["layers"]["moe"] = out
    return tree


# --- placement history (relayout × checkpoint/restart consistency) ---------
# Checkpoints save params in whatever expert layout was active at that step;
# restoring one MUST re-establish that layout or every lane silently applies
# the wrong experts' weights.  The history sidecar records (active_from_step,
# placement table) pairs in the checkpoint dir; restarts look up the table
# active at the committed step.

def _history_path(ckpt_dir: str) -> str:
    return os.path.join(ckpt_dir, "placement_history.npz")


def save_placement_history(ckpt_dir: str, history, node_size: int) -> None:
    """history: list of (active_from_step, placement).  Written synchronously
    at every relayout, so any checkpoint committed later can be re-based."""
    os.makedirs(ckpt_dir, exist_ok=True)
    np.savez(_history_path(ckpt_dir),
             steps=np.array([s for s, _ in history], np.int64),
             tables=np.stack([relayout.placement_table(p)
                              for _, p in history]),
             node_size=np.int64(node_size))


def load_placement_history(ckpt_dir: str, n_experts: int):
    """-> list of (active_from_step, placement) or None when never relayouted."""
    path = _history_path(ckpt_dir)
    if not os.path.exists(path):
        return None
    z = np.load(path)
    ns = int(z["node_size"])
    return [(int(s), relayout.TablePlacement(tbl, node_size=ns,
                                             n_experts=n_experts))
            for s, tbl in zip(z["steps"], z["tables"])]


def placement_at_step(history, step: int):
    """The placement whose layout a checkpoint committed at ``step`` holds:
    the last history entry with active_from <= step."""
    active = [p for s, p in history if s <= step]
    return active[-1] if active else history[0][1]


# --- traffic-EMA sidecar (warm relayout resume) -----------------------------
# The placement table is persisted (placement_history.npz) but the EMA that
# *produced* it used to restart cold on every resume, leaving the first
# post-restart relayout to re-solve from a near-empty signal.  The EMA is
# pure replicated state, so a small sidecar written at the checkpoint cadence
# resumes it warm; like any EMA it tolerates the (<= ckpt_every steps of)
# staleness between the sidecar and the committed step it rewinds to.

def _traffic_path(ckpt_dir: str) -> str:
    return os.path.join(ckpt_dir, "traffic_ema.npz")


def save_traffic_state(ckpt_dir: str, traffic, step: int) -> None:
    """Persist the EMA accumulators next to the checkpoints (synchronous —
    the arrays are (L, E)/(L, EP) floats, noise next to a weight save)."""
    os.makedirs(ckpt_dir, exist_ok=True)
    np.savez(_traffic_path(ckpt_dir), step=np.int64(step),
             **{k: np.asarray(v) for k, v in traffic._asdict().items()})


def load_traffic_state(ckpt_dir: str, like):
    """-> (TrafficState, saved_step) matching ``like``'s shapes, or None when
    there is no sidecar or it was written for a different model shape.

    Fields ``like`` has but the sidecar lacks are zero-filled: a sidecar
    written before the state grew a field (e.g. the commplan lane→node
    matrix) still resumes warm — the missing accumulator restarts cold and
    re-warms within its EMA horizon, instead of discarding the whole state
    (or worse, crashing the resume).  A PRESENT key with the wrong shape
    still means a different model and returns None.
    """
    path = _traffic_path(ckpt_dir)
    if not os.path.exists(path):
        return None
    z = np.load(path)
    leaves = {}
    for k, want in like._asdict().items():
        if k not in z:
            leaves[k] = jnp.zeros_like(want)
            continue
        if z[k].shape != tuple(want.shape):
            return None
        leaves[k] = jnp.asarray(z[k].astype(np.asarray(want).dtype))
    return type(like)(**leaves), int(z["step"])


def apply_relayout(params, opt, traffic_state, ctx, *, slots_per_lane=None,
                   log=print):
    """Between-steps placement swap: solve a table placement from the EMA
    expert loads (summed over layers), then gather-migrate the expert weight
    blocks AND their optimizer moments/master copies so training continues
    bit-compatibly (the loss is invariant under re-layout — only which lane
    hosts which expert changes).  Returns (params, opt, new_ctx, stats)."""
    old = ctx.placement
    loads = np.asarray(traffic_state.expert_ema)
    if loads.ndim > 1:                     # per-layer stacked state
        loads = loads.sum(axis=0)
    new = relayout.solve_placement(
        loads, ep=old.ep, node_size=old.node_size,
        slots_per_lane=slots_per_lane or old.experts_per_lane)
    w1 = params["layers"]["moe"]["w1"]
    d, f = w1.shape[-2], w1.shape[-1]
    n_layers = w1.shape[0]
    row_bytes = n_layers * (2 * d * f + f * d) * w1.dtype.itemsize
    stats = relayout.migration_stats(old, new, row_bytes=row_bytes)
    params = _migrate_moe_tree(params, old, new)
    opt = adamw.AdamWState(
        opt.step,
        _migrate_moe_tree(opt.mu, old, new),
        _migrate_moe_tree(opt.nu, old, new),
        _migrate_moe_tree(opt.master, old, new))
    mx_old = float(relayout.lane_loads(loads, old).max())
    mx_new = float(relayout.lane_loads(loads, new).max())
    log(f"relayout: max-lane load {mx_old:.1f} -> {mx_new:.1f}, "
        f"{stats['rows_moved']}/{stats['slots']} expert blocks moved "
        f"({stats['bytes_moved'] / 1e6:.2f} MB)", flush=True)
    return params, opt, dataclasses.replace(ctx, placement=new), stats


class TrainResult(NamedTuple):
    params: Any
    opt: Any
    losses: list          # every step's loss, in order, replays included
    run: RunState


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-moe-30b-a3b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-sized variant of the arch (CPU)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to N layers (widths unchanged)")
    ap.add_argument("--engine", default="fused_hier",
                    help="dComm engine for the MoE shuffle (fused_flat | "
                         "fused_pipe | fused_hier | disagg | ragged), or "
                         "'auto' to let the comm-path policy "
                         "(core/commplan.py) pick flat vs hier PER LAYER "
                         "from the online traffic stats at each relayout "
                         "boundary (moe family; needs --relayout-every). "
                         "Naming an engine is the manual override: the "
                         "policy never touches it")
    ap.add_argument("--dedup", action="store_true",
                    help="dispatch-side dedup/condense: ship one wire row "
                         "per distinct (token, dest lane) pair and expand "
                         "on the landing side (fused_flat engine, incl. "
                         "flat layers under --engine auto)")
    ap.add_argument("--seq-migrate", action="store_true",
                    help="sequence migration: rebalance whole sequences "
                         "across data ranks per batch (LPT over a per-"
                         "sequence routing-diversity proxy — distinct-token "
                         "count), with relayout-style bytes-moved "
                         "accounting")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="checkpoint cadence in steps, plus the last step; "
                         "0 writes no checkpoint")
    ap.add_argument("--data", default="zipf", choices=["zipf", "uniform"])
    ap.add_argument("--capacity-factor", type=float, default=2.0)
    ap.add_argument("--inject-failure-at", type=int, default=None)
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="step failures the runtime restarts from the last "
                         "checkpoint before it re-raises (0: the first "
                         "failure ends the run)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--moe-stream", type=int, default=0,
                    help="moe_ffn/moe_tx families: layers per cross-layer "
                         "stream block (fused_pipe overlaps combine of layer "
                         "i with dispatch of layer i+1 inside a block; for "
                         "moe_tx the tail additionally rides across the "
                         "attention block — this is the moe-tx-stream knob); "
                         "0 = per-layer islands")
    ap.add_argument("--moe-interleave", type=int, default=1,
                    help="moe_ffn/moe_tx families: token micro-batches "
                         "interleaved through each stream block (K lanes "
                         "round-robin through one schedule — lane j+1's "
                         "compute fills lane j's boundary window); must "
                         "divide the per-shard batch; 1 = plain chained "
                         "stream")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation micro-batches; when it "
                         "equals --moe-interleave on a moe_ffn arch the "
                         "micro-batches feed the interleaved stream as its "
                         "lanes instead of a serial scan")
    ap.add_argument("--pipe-slices", type=int, default=0,
                    help="fused_pipe slice count; 0 = auto via pipesim")
    ap.add_argument("--relayout-every", type=int, default=0,
                    help="moe family: every N steps, re-solve the expert "
                         "placement from the online EMA traffic stats and "
                         "migrate the expert weight blocks (0 = static "
                         "placement); stats are collected either way")
    ap.add_argument("--traffic-decay", type=float, default=0.99,
                    help="EMA decay of the online traffic statistics")
    ap.add_argument("--calibrate", action="store_true",
                    help="measure pipe stage/wire/overhead constants on this "
                         "platform before building the context (replaces the "
                         "paper's A100/CX-7 defaults in pipesim + commplan)")
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers is not None:
        print(f"depth cut: {cfg.name} {cfg.n_layers} -> {args.layers} layers",
              flush=True)
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    enable_compile_cache()
    mesh = make_host_mesh()
    # --engine auto: the comm-path policy replans per layer at relayout
    # boundaries; until the first plan (cold EMA) every layer runs the
    # default engine below.  Only the moe family has per-layer islands —
    # stream families share one schedule per block and stay single-engine.
    auto_engine = args.engine == "auto"
    if auto_engine and cfg.family != "moe":
        print(f"[commplan] --engine auto needs per-layer MoE islands "
              f"(family {cfg.family!r}); falling back to fused_hier",
              flush=True)
        auto_engine = False
    base_engine = "fused_hier" if args.engine == "auto" else args.engine
    calibration = None
    if args.calibrate:
        from repro.core import calibrate as calibrate_lib
        calibration = calibrate_lib.calibrate()
        wire = (f"{calibration.wire_bw / 1e9:.1f} GB/s"
                if calibration.wire_measured else
                f"not measured (one device; assumed "
                f"{calibration.wire_bw / 1e9:.1f} GB/s = stage / 4)")
        print(f"[calibrate] {calibration.platform}: "
              f"stage {calibration.stage_bw / 1e9:.1f} GB/s, "
              f"wire {wire}, "
              f"overhead {calibration.overhead_s * 1e6:.1f} us", flush=True)
    ctx = make_context(cfg, mesh, multi_pod=False, engine=base_engine,
                       capacity_factor=args.capacity_factor,
                       node_size=max(1, mesh.shape["model"] // 2),
                       moe_stream=args.moe_stream,
                       moe_interleave=args.moe_interleave,
                       pipe_slices=args.pipe_slices,
                       traffic_decay=args.traffic_decay,
                       dedup=args.dedup, calibration=calibration)
    # resuming a run that relayouted: the checkpoint's weights are laid out
    # per the placement-history sidecar, not the arithmetic map
    if cfg.moe is not None and cfg.family in ("moe", "moe_ffn", "moe_tx"):
        history = load_placement_history(args.ckpt_dir, cfg.moe.n_experts)
        committed = checkpointer.latest_step(args.ckpt_dir)
        if history is not None and committed is not None:
            ctx = dataclasses.replace(
                ctx, placement=placement_at_step(history, committed))
            print(f"[relayout] resuming with the placement active at "
                  f"committed step {committed}", flush=True)
    bundle = zoo.build(cfg, ctx)

    key = jax.random.PRNGKey(0)
    with mesh:
        params = sh.init_params_sharded(bundle.init, key, mesh,
                                        fsdp_experts=ctx.fsdp_experts)
        opt = adamw.init(params)
        opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=max(5, args.steps // 20),
                                    total_steps=args.steps)
        step_fn = jax.jit(make_train_step(bundle, opt_cfg, accum=args.accum),
                          donate_argnums=(0, 1))

        # online traffic stats: per-layer EMA state threaded through the MoE
        # islands (moe family per-layer, moe_ffn per stream block); feeds the
        # hier balancer every step and the load-adaptive re-layout at the
        # --relayout-every cadence.
        traffic = None
        serial_accum = (args.accum > 1
                        and not steps_mod.accum_fuses_into_stream(bundle,
                                                                  args.accum))
        if cfg.moe is not None and cfg.family in ("moe", "moe_ffn", "moe_tx"):
            if serial_accum:
                # the serial microbatch scan does not thread traffic state
                # yet; the fused path (--moe-interleave == --accum on a
                # moe_ffn/fused_pipe arch) does
                print("[traffic] stats disabled under serial gradient "
                      "accumulation", flush=True)
            else:
                traffic = traffic_lib.init_traffic_state(
                    cfg.moe.n_experts, ctx.placement.ep,
                    n_layers=cfg.n_layers)
                # warm EMA resume: only when there is a committed checkpoint
                # to resume (a stale sidecar from a dead run must not seed a
                # fresh one); the sidecar rides the checkpoint cadence, so
                # the first post-resume relayout sees a real load signal
                if checkpointer.latest_step(args.ckpt_dir) is not None:
                    warm = load_traffic_state(args.ckpt_dir, traffic)
                    if warm is not None:
                        traffic, tstep = warm
                        print(f"[traffic] resumed EMA state saved at step "
                              f"{tstep}", flush=True)
        box = {"ctx": ctx, "bundle": bundle, "step_fn": step_fn,
               "traffic": traffic, "n": 0, "fence": False,
               "history": [(0, ctx.placement)],
               "seq_rows": 0, "seq_bytes": 0}

        def rebuild(new_ctx):
            box["ctx"] = new_ctx
            box["bundle"] = zoo.build(cfg, new_ctx)
            box["step_fn"] = jax.jit(
                make_train_step(box["bundle"], opt_cfg, accum=args.accum),
                donate_argnums=(0, 1))
            # the next call pays XLA recompilation — fence it off from the
            # runtime's straggler monitor (compile time is not lane health)
            box["fence"] = True

        def on_restart(step, restored):
            """Re-base the adaptive-placement state after a rewind: the
            restored checkpoint's weights carry the layout that was active at
            ``step``, and the relayout cadence counter must rewind with the
            replayed stream.  EMA stats resume from the sidecar when one was
            written (warm), else restart cold and re-warm within their
            horizon (DESIGN.md §traffic)."""
            box["n"] = step
            if box["traffic"] is not None:
                cold = traffic_lib.init_traffic_state(
                    cfg.moe.n_experts, box["ctx"].placement.ep,
                    n_layers=cfg.n_layers)
                warm = load_traffic_state(args.ckpt_dir, cold)
                box["traffic"] = warm[0] if warm is not None else cold
            if restored:
                # drop relayouts newer than the committed step, match layout
                box["history"] = [(s, p) for s, p in box["history"]
                                  if s <= step] or box["history"][:1]
                want = placement_at_step(box["history"], step)
                if want is not box["ctx"].placement:
                    rebuild(dataclasses.replace(box["ctx"], placement=want))
            else:
                # params were KEPT (no committed checkpoint): the current
                # layout stays live and is what any future checkpoint saves
                box["history"] = [(0, box["ctx"].placement)]
            if args.relayout_every:
                save_placement_history(args.ckpt_dir, box["history"],
                                       box["ctx"].placement.node_size)

        src_cls = ZipfNgramLM if args.data == "zipf" else SyntheticLM
        source = src_cls(cfg.vocab, args.seq, args.batch)
        ispecs = {k: v for k, v in source.batch_at(0).items()}
        bspecs = batch_specs(cfg, "train", ctx,
                             {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                              for k, v in ispecs.items()})
        bshard = {k: NamedSharding(mesh, s) for k, s in bspecs.items()}

        n_data_ranks = mesh.shape["data"]

        def batch_at(step):
            host = source.batch_at(step)
            if args.seq_migrate and n_data_ranks > 1:
                # per-sequence routing-diversity proxy: sequences touching
                # more distinct tokens route to more experts/nodes (the
                # CPU-honest stand-in for measured per-sequence send load)
                tok = np.asarray(host["tokens"])
                loads = np.array([np.unique(row).size for row in tok],
                                 np.float64)
                row_bytes = sum(np.asarray(v)[0].nbytes
                                for v in host.values()
                                if np.asarray(v).shape[:1] == tok.shape[:1])
                perm, stats = commplan.plan_sequence_migration(
                    loads, n_data_ranks, row_bytes=row_bytes)
                if stats["rows_moved"]:
                    host = {k: (v[perm]
                                if np.asarray(v).shape[:1] == tok.shape[:1]
                                else v)
                            for k, v in host.items()}
                box["seq_rows"] += stats["rows_moved"]
                box["seq_bytes"] += stats["bytes_moved"]
            return {k: jax.device_put(v, bshard[k]) for k, v in host.items()}

        t_hist = []
        losses = []

        def wrapped(params, opt, batch):
            t0 = time.perf_counter()
            if box["traffic"] is not None:
                params, opt, metrics = box["step_fn"](params, opt, batch,
                                                      box["traffic"])
                box["traffic"] = metrics.pop("traffic")
            else:
                params, opt, metrics = box["step_fn"](params, opt, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            t_hist.append(time.perf_counter() - t0)
            n = len(t_hist)
            box["n"] += 1
            if box["fence"]:
                box["fence"] = False
                metrics["straggler_fence"] = True
            if n % args.log_every == 1:
                print(f"step {n:5d}  loss {loss:.4f}  "
                      f"{np.mean(t_hist[-args.log_every:]):.3f}s/step", flush=True)
                if args.seq_migrate:
                    print(f"[seqmig] {box['seq_rows']} sequences moved "
                          f"({box['seq_bytes'] / 1e6:.2f} MB) so far",
                          flush=True)
            if (args.relayout_every and box["traffic"] is not None
                    and box["n"] % args.relayout_every == 0):
                # comm-path policy BEFORE the swap: the EMA send matrices
                # were measured under the placement being retired
                decisions = None
                if auto_engine:
                    decisions = commplan.plan_paths(
                        box["traffic"], box["ctx"].placement,
                        row_bytes=cfg.d_model * 2,   # one bf16 token row
                        costs=commplan.LinkCosts.from_dcomm(box["ctx"].dcfg),
                        dedup=args.dedup, default=base_engine)
                    summ = commplan.summarize_decisions(decisions)
                    print(f"[commplan] step {box['n']}: "
                          f"{summ['n_flat']} flat / {summ['n_hier']} hier "
                          f"layers ({summ['n_cold']} cold) — "
                          + " ".join(f"L{i}:{'F' if e == 'fused_flat' else 'H'}"
                                     for i, e in enumerate(summ["per_layer"])),
                          flush=True)
                params, opt, new_ctx, _ = apply_relayout(
                    params, opt, box["traffic"], box["ctx"])
                if decisions is not None:
                    new_ctx = dataclasses.replace(
                        new_ctx,
                        engines=tuple(d.engine for d in decisions))
                # expert counts stay valid across the swap, but the per-lane
                # EMAs (send rows, lane→node matrix, condensed rows) were
                # measured under the OLD table — restart them cold rather
                # than misattribute forwarder load for an EMA horizon
                box["traffic"] = box["traffic"]._replace(
                    lane_send_ema=jnp.zeros_like(box["traffic"].lane_send_ema),
                    lane_node_ema=jnp.zeros_like(box["traffic"].lane_node_ema),
                    lane_cond_ema=jnp.zeros_like(box["traffic"].lane_cond_ema))
                # the placement table is baked into the jitted step — re-jit;
                # amortized over the relayout cadence (DESIGN.md §traffic)
                rebuild(new_ctx)
                # the new layout is active from this step on: any checkpoint
                # committed at step >= box["n"] holds it — record BEFORE the
                # runtime can save one
                box["history"].append((box["n"], new_ctx.placement))
                save_placement_history(args.ckpt_dir, box["history"],
                                       new_ctx.placement.node_size)
            # EMA sidecar rides the checkpoint cadence: any committed
            # checkpoint finds an EMA no staler than one cadence.  Written
            # AFTER the relayout block so that when the two cadences
            # coincide the sidecar holds the post-reset lane-send EMA — a
            # resume must not feed Algorithm 1 loads measured under the
            # table the relayout just replaced.
            if (box["traffic"] is not None and args.ckpt_every
                    and (box["n"] % args.ckpt_every == 0
                         or box["n"] == args.steps)):
                save_traffic_state(args.ckpt_dir, box["traffic"], box["n"])
            return params, opt, metrics

        rcfg = RunConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every,
                         inject_failure_at=args.inject_failure_at,
                         max_restarts=args.max_restarts,
                         on_restart=on_restart)
        (params, opt), run = run_training(wrapped, (params, opt), batch_at, rcfg)
        print(f"done: {run.steps_run} steps, {run.restarts} restarts, "
              f"{run.straggler_events} straggler events")
    return TrainResult(params, opt, losses, run)


if __name__ == "__main__":
    main()
