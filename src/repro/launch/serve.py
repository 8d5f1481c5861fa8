"""Serving driver: batched prefill + decode with the FUSCO dispatch in the
prefill path (TTFT — the paper's inference metric).

Compilation is separated from latency: both paths AOT-compile (or warm up)
before the clock starts and report ``compile_s`` on its own line, so TTFT is
the paper's first-token latency rather than first-token-plus-jit.

``python -m repro.launch.serve --arch <id> --reduced --requests 8 --gen 16``
``python -m repro.launch.serve ... --continuous`` drives the per-slot
continuous-batching engine instead of one lock-step batch.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import zoo
from repro.models.lm import make_context
from repro.parallel.sharding import init_params_sharded
from repro.serving.engine import ContinuousServingEngine, greedy


def _run_continuous(bundle, params, args, max_len):
    # every prompt the launcher submits is --prompt-len long: one bucket
    eng = ContinuousServingEngine(bundle, max_batch=args.requests,
                                  max_len=max_len,
                                  buckets=(args.prompt_len,))
    compile_s = eng.warmup(params)
    rng = jax.random.PRNGKey(1)
    for i in range(args.requests):
        toks = jax.random.randint(jax.random.fold_in(rng, i),
                                  (args.prompt_len,), 0, bundle.cfg.vocab)
        eng.submit(toks, max_new=args.gen)
    done = eng.run(params)
    st = eng.stats()
    print(f"compile {compile_s:.2f} s  ({eng.compile_count} executables)")
    print(f"ttft p50 {st['p50_ttft_s']*1e3:.1f} ms  "
          f"p99 {st['p99_ttft_s']*1e3:.1f} ms   "
          f"decode {st['decode_tok_s']:.0f} tok/s   "
          f"occupancy {st['mean_slot_occupancy']:.2f}  "
          f"({len(done)} requests)")
    print("sample:", done[0].output[:12])
    return done


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-moe-30b-a3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to N layers (widths unchanged)")
    ap.add_argument("--engine", default="fused_hier")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--continuous", action="store_true",
                    help="serve via the per-slot continuous-batching engine "
                         "instead of one lock-step batch")
    ap.add_argument("--moe-stream", type=int, default=0,
                    help="moe_ffn/moe_tx families: layers per cross-layer "
                         "stream block")
    ap.add_argument("--moe-interleave", type=int, default=1,
                    help="moe_ffn/moe_tx families: prefill requests "
                         "interleaved as micro-batch lanes through each "
                         "stream block (must divide --requests)")
    args = ap.parse_args(argv)
    if args.requests % max(1, args.moe_interleave) != 0:
        ap.error("--moe-interleave must divide --requests")

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers is not None:
        print(f"depth cut: {cfg.name} {cfg.n_layers} -> {args.layers} layers")
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    enable_compile_cache()
    mesh = make_host_mesh()
    ctx = make_context(cfg, mesh, multi_pod=False, engine=args.engine,
                       node_size=max(1, mesh.shape["model"] // 2),
                       moe_stream=args.moe_stream,
                       moe_interleave=args.moe_interleave)
    bundle = zoo.build(cfg, ctx)
    key = jax.random.PRNGKey(0)
    max_len = args.prompt_len + args.gen

    def init(k):
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                            if x.dtype == jnp.float32 else x, bundle.init(k))

    with mesh:
        params = init_params_sharded(init, key, mesh,
                                     fsdp_experts=ctx.fsdp_experts)
        if args.continuous:
            if cfg.family == "encdec":
                ap.error("--continuous supports decoder-only families")
            return _run_continuous(bundle, params, args, max_len)

        batch = zoo.make_smoke_batch(cfg, key, args.requests, args.prompt_len)
        if cfg.family == "encdec":
            batch = {"frames": batch["frames"], "tokens": batch["tokens"][:, 0]}

        # greedy sampling runs inside both executables
        prefill = jax.jit(lambda p, b: greedy(bundle.prefill(p, b, max_len)))
        decode = jax.jit(lambda p, st, t: greedy(
            bundle.decode_step(p, st, t, max_len)))

        # warm up both executables (two decode steps cover the state-sharding
        # variants the jit caches) before the clock starts, so TTFT is
        # latency, not latency + jit
        t0 = time.perf_counter()
        tok, state = prefill(params, batch)
        for _ in range(2):
            tok, state = decode(params, state, tok)
        jax.block_until_ready(tok)
        compile_s = time.perf_counter() - t0
        print(f"compile+warmup {compile_s:.2f} s")

        t0 = time.perf_counter()
        tok, state = prefill(params, batch)
        jax.block_until_ready(tok)
        ttft = time.perf_counter() - t0
        seqs = [tok]
        t0 = time.perf_counter()
        for _ in range(args.gen - 1):
            tok, state = decode(params, state, tok)
            seqs.append(tok)
        jax.block_until_ready(tok)
        t_dec = time.perf_counter() - t0
        out = jnp.stack(seqs, 1)
        print(f"ttft {ttft*1e3:.1f} ms   decode {t_dec/(args.gen-1)*1e3:.1f} ms/tok  "
              f"({args.requests} requests)")
        print("sample:", out[0][:12].tolist())
    return out


if __name__ == "__main__":
    main()
