#!/usr/bin/env python3
"""Smoke test of the main path on a TPU: kernels, serving and training.

    python chip_smoke.py               # one chip: the three phases below
    python chip_smoke.py --four-chips  # four chips: expert parallelism only

One process drives every phase on the chips it sees; it refuses to run
without a TPU.  Default phases, each at a model's published widths:

  (a) kernels  every Pallas kernel once at qwen3-moe-30b-a3b widths
               (interpret=False), compared with its plain oracle;
  (b) serve    ``repro.launch.serve`` continuous serving of
               qwen3-moe-30b-a3b cut to 4 layers, 8 requests x 16 tokens;
  (c) train    ``repro.launch.train`` on moe-tx-stream (attention stream,
               fused_pipe) cut to 2 layers, 5 steps, no restarts allowed.

``--four-chips`` runs qwen3-moe-30b-a3b cut to 2 layers on a data=1 x
model=4 mesh (32 experts per chip, two virtual nodes of two chips): the
prefill logits of fused_hier and fused_flat against the disagg baseline,
and 3 fused_hier train steps whose first loss is compared with disagg's.

Any failure exits nonzero.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SERVE_ARCH = "qwen3-moe-30b-a3b"
TRAIN_ARCH = "moe-tx-stream"

# max |kernel - oracle| / max |oracle|
TOL = {"segment_gather": 0.0, "segment_scatter_add": 1e-2,
       "fused_swiglu": 2e-2, "grouped_matmul": 2e-2,
       "flash_attention": 2e-2}
LOGIT_TOL = 1e-3       # f32 engines vs the disagg baseline, relative
LOSS_TOL = 1e-2        # step-1 loss, absolute


def log(msg):
    print(msg, flush=True)


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def check(name, value, ok):
    log(f"  {name}: {value}")
    if not ok:
        raise AssertionError(f"{name} out of bounds: {value}")


# ------------------------------------------------------------- phase (a) ---

def kernel_phase(cfg, *, tokens=512, capacity=64, seq=1024, interpret=False):
    """Each Pallas kernel once at ``cfg``'s widths on seeded data, against
    its oracle computed in f32 at full matmul precision."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.fused_staging import fused_swiglu_pallas
    from repro.kernels.grouped_matmul import grouped_matmul
    from repro.kernels.segment_gather import segment_gather
    from repro.kernels.segment_scatter_add import segment_scatter_add
    from repro.layers.attention import reference_attention

    d, f, e = cfg.d_model, cfg.moe.d_ff_expert, cfg.moe.n_experts
    rows = tokens * cfg.moe.top_k
    bf, f32 = jnp.bfloat16, jnp.float32
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 16))
    normal = lambda shape, s=1.0: (jax.random.normal(next(ks), shape)
                                   * s).astype(bf)
    up = lambda *xs: [x.astype(f32) for x in xs]
    errs = {}

    def compare(name, got, want_fn):
        # the oracle at full f32 matmul precision; the kernel as it runs
        # (a precision context would reach into the kernel's own dots)
        with jax.default_matmul_precision("highest"):
            want = want_fn()
        errs[name] = rel_err(got, want)

    src = normal((tokens, d))
    idx = jax.random.randint(next(ks), (rows,), -1, tokens)
    compare("segment_gather", segment_gather(src, idx, interpret=interpret),
            lambda: ref.segment_gather_ref(src, idx))

    # random destinations: every token row is hit ~top_k times, from rows
    # far apart (non-consecutive revisits), and some rows are dropped (-1)
    rsrc = normal((rows, d))
    dst = jax.random.randint(next(ks), (rows,), -1, tokens)
    gates = jax.random.uniform(next(ks), (rows,))
    compare("segment_scatter_add",
            segment_scatter_add(rsrc, dst, gates, tokens,
                                interpret=interpret),
            lambda: ref.segment_scatter_add_ref(*up(rsrc), dst, gates,
                                                tokens))

    x = normal((1, e, capacity, d), 0.5)
    w1, w3 = normal((e, d, f), d ** -0.5), normal((e, d, f), d ** -0.5)
    w2 = normal((e, f, d), f ** -0.5)
    counts = jax.random.randint(next(ks), (1, e), 0, capacity + 1)
    compare("fused_swiglu",
            fused_swiglu_pallas(x, w1, w3, w2, counts, interpret=interpret),
            lambda: ref.fused_swiglu_ref(*up(x, w1, w3, w2), counts))
    compare("grouped_matmul",
            grouped_matmul(x[0], w1, counts[0], interpret=interpret),
            lambda: ref.grouped_matmul_ref(*up(x[0], w1), counts[0]))

    # a shifted q chunk (the second half of the sequence) against all keys
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = normal((2, seq // 2, hq, hd))
    k, v = normal((2, seq, hkv, hd)), normal((2, seq, hkv, hd))
    qpos = jnp.arange(seq // 2, seq, dtype=jnp.int32)
    kpos = jnp.arange(seq, dtype=jnp.int32)
    compare("flash_attention",
            flash_attention(q, k, v, qpos, kpos, True, None, 256, 256,
                            interpret),
            lambda: reference_attention(*up(q, k, v), qpos, kpos,
                                        causal=True))
    for name, err in errs.items():
        check(f"{name} max rel err (tol {TOL[name]})", err, err <= TOL[name])
    return errs


# ------------------------------------------------------------- phase (b) ---

def serve_phase(argv, requests, gen, vocab):
    from repro.launch import serve
    log(f"  serve argv: {' '.join(argv)}")
    done = serve.main(argv)
    lens = sorted(len(r.output) for r in done)
    check("requests served", len(done), len(done) == requests)
    check("tokens per request", lens, lens == [gen] * requests)
    bad = [t for r in done for t in r.output if not 0 <= t < vocab]
    check("tokens outside the vocabulary", len(bad), not bad)
    return done


# ------------------------------------------------------------- phase (c) ---

def train_phase(argv, steps):
    """No restarts are allowed, so no checkpoint is written (a full-width
    save would move the whole optimizer state to the host)."""
    from repro.launch import train
    with tempfile.TemporaryDirectory() as ckpt:
        argv = argv + ["--ckpt-dir", ckpt, "--ckpt-every", "0"]
        log(f"  train argv: {' '.join(argv)}")
        res = train.main(argv)
    check("losses", res.losses, len(res.losses) == steps
          and all(math.isfinite(x) for x in res.losses))
    check("restarts", res.run.restarts, res.run.restarts == 0)
    return res.losses


# ------------------------------------------------------- four-chip phase ---

def four_chip_phase(cfg, train_argv, batch=8, seq=128, capacity=16.0):
    """Expert parallelism over four chips: fused_hier and fused_flat prefill
    logits and the fused_hier step-1 loss, each against disagg.

    Capacity is 16x the mean per-expert load, so that no engine drops a
    token (fused_hier's second-level buffers drop first; at 4x they do on
    seeded data) and all of them compute one function.  The logits are
    compared in f32 at full matmul precision: in bf16 the engines' rounding
    differs (Pallas staging vs the XLA baseline), which flips near-tied
    top-k routes in the second layer and moves whole tokens' outputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import make_host_mesh
    from repro.models import zoo
    from repro.models.lm import make_context
    from repro.parallel.sharding import init_params_sharded

    mesh = make_host_mesh(data=1, model=4)
    log(f"  mesh {dict(mesh.shape)}, {cfg.name} at {cfg.n_layers} layers")
    engines = ("disagg", "fused_hier", "fused_flat")
    bundles = {}
    for e in engines:
        ctx = make_context(cfg, mesh, multi_pod=False, engine=e, node_size=2,
                           capacity_factor=capacity)
        bundles[e] = zoo.build(cfg, dataclasses.replace(
            ctx, compute_dtype=jnp.float32))
    ctx = bundles["disagg"].ctx
    log(f"  ep {ctx.placement.ep}, experts per chip "
        f"{ctx.placement.experts_per_lane}, node_size "
        f"{ctx.placement.node_size}, capacity factor {capacity}")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab)
    logits = {}
    with mesh, jax.default_matmul_precision("highest"):
        params = init_params_sharded(bundles["disagg"].init,
                                     jax.random.PRNGKey(0), mesh,
                                     fsdp_experts=ctx.fsdp_experts)
        for e, b in bundles.items():
            t0 = time.perf_counter()
            fn = jax.jit(lambda p, t, b=b: b.prefill(p, {"tokens": t},
                                                     seq)[0])
            logits[e] = np.asarray(fn(params, tokens), np.float32)
            log(f"  prefill {e} (f32): {time.perf_counter() - t0:.1f} s "
                f"(compile included)")
        del params
    errs = {e: rel_err(logits[e], logits["disagg"]) for e in engines[1:]}
    for e, err in errs.items():
        log(f"  prefill logits {e} vs disagg max rel err: {err}")
    for e, err in errs.items():
        check(f"prefill logits {e} vs disagg (tol {LOGIT_TOL})", err,
              err <= LOGIT_TOL)

    base = train_argv + ["--batch", str(batch), "--seq", str(seq),
                         "--capacity-factor", str(capacity),
                         "--max-restarts", "0", "--log-every", "1"]
    hier = train_phase(base + ["--engine", "fused_hier", "--steps", "3"], 3)
    ref = train_phase(base + ["--engine", "disagg", "--steps", "1"], 1)
    check(f"step-1 loss fused_hier {hier[0]} vs disagg {ref[0]} "
          f"(abs tol {LOSS_TOL})", abs(hier[0] - ref[0]),
          abs(hier[0] - ref[0]) <= LOSS_TOL)


# ------------------------------------------------------------------ main ---

def peak_bytes(devices):
    return [d.memory_stats().get("peak_bytes_in_use") for d in devices]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip expert-parallel path")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no src/repro next to {__file__}; "
                         "run it from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform} ({len(devices)} devices)")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        raise SystemExit(f"chip_smoke: needs {want} chips, found "
                         f"{len(devices)}")

    from repro.configs import get_arch
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh

    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    log(f"compile cache: {enable_compile_cache()}")
    mesh = make_host_mesh()
    if any(d.platform != "tpu" for d in mesh.devices.flat):
        raise SystemExit("chip_smoke: the mesh holds non-TPU devices")

    phases = []
    serve_cfg = get_arch(SERVE_ARCH)
    if args.four_chips:
        layers = 2
        phases.append(("four-chip expert parallelism", lambda: four_chip_phase(
            dataclasses.replace(serve_cfg, n_layers=layers),
            ["--arch", SERVE_ARCH, "--layers", str(layers)])))
    else:
        requests, gen = 8, 16
        serve_argv = ["--arch", SERVE_ARCH, "--continuous", "--engine",
                      "fused_hier", "--requests", str(requests),
                      "--prompt-len", "64", "--gen", str(gen),
                      "--layers", "4"]
        train_argv = ["--arch", TRAIN_ARCH, "--engine", "fused_pipe",
                      "--moe-stream", "2", "--layers", "2", "--steps", "5",
                      "--max-restarts", "0", "--log-every", "1"]
        phases += [
            ("(a) kernels", lambda: kernel_phase(serve_cfg)),
            ("(b) serve", lambda: serve_phase(serve_argv, requests, gen,
                                              serve_cfg.vocab)),
            ("(c) train", lambda: train_phase(train_argv, 5)),
        ]
    for name, fn in phases:
        log(f"== {name}")
        t0 = time.perf_counter()
        fn()
        log(f"  {name}: {time.perf_counter() - t0:.1f} s wall "
            f"(compile included)")
        log(f"  peak_bytes_in_use per device: "
            f"{peak_bytes(devices[:want])}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
