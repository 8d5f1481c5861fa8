"""Continuous (per-slot) serving engine: admission, retirement, compile
accounting, TTFT regression, and the batch-1 conformance oracle."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.compat import make_mesh

from engine_harness import serving_stream_oracle
from repro.configs import get_arch
from repro.models import zoo
from repro.models.lm import make_context
from repro.serving.engine import ContinuousServingEngine, ServingEngine


def _bundle(family):
    """A reduced bundle + mesh per family; moe_ffn runs its interleaved
    stream (K=2 lanes drawn from the admission chunk)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    if family == "moe":
        cfg = get_arch("qwen3-moe-30b-a3b").reduced()
        kw = dict(engine="fused_flat")
    elif family == "moe_ffn":
        cfg = dataclasses.replace(get_arch("moe-ffn-stream").reduced(),
                                  n_layers=2)
        kw = dict(engine="fused_pipe", capacity_factor=4.0, node_size=1,
                  moe_stream=2, moe_interleave=2)
    elif family == "moe_tx":
        cfg = dataclasses.replace(get_arch("moe-tx-stream").reduced(),
                                  n_layers=2)
        kw = dict(engine="fused_pipe", capacity_factor=4.0, node_size=1,
                  moe_stream=2)
    else:
        cfg = get_arch("qwen3-1.7b").reduced()
        kw = {}
    ctx = make_context(cfg, mesh, multi_pod=False, **kw)
    bundle = zoo.build(cfg, ctx)
    return bundle, bundle.init(jax.random.PRNGKey(0)), mesh, cfg


def test_continuous_completes_refills_and_reports_stats():
    bundle, params, mesh, cfg = _bundle("dense")
    emitted = []
    eng = ContinuousServingEngine(bundle, max_batch=2, max_len=48,
                                  buckets=(16, 32), emit=emitted.append)
    r = np.random.default_rng(0)
    with mesh:
        eng.warmup(params)
        for i in range(5):
            eng.submit(r.integers(0, cfg.vocab, (8 + 3 * i,)),
                       max_new=3 + i % 3)
        done = eng.run(params)
    # 5 requests through 2 slots: slots retired and refilled mid-run
    assert len(done) == len(emitted) == 5
    for q in done:
        assert q.done and q.ttft_s is not None and q.ttft_s > 0
        assert 1 <= len(q.output) <= q.max_new
        assert all(0 <= t < cfg.vocab for t in q.output)
    st = eng.stats()
    assert st["requests"] == 5
    for k in ("p50_ttft_s", "p95_ttft_s", "p99_ttft_s", "compile_s",
              "mean_slot_occupancy", "decode_tok_s"):
        assert k in st, k
    assert st["p50_ttft_s"] <= st["p99_ttft_s"]
    assert 0 < st["mean_slot_occupancy"] <= 1


def test_continuous_zero_steady_state_recompiles():
    """After warmup, NO admission pattern whose prompts fit the buckets may
    compile anything — the acceptance criterion for bucketed AOT prefill."""
    bundle, params, mesh, cfg = _bundle("moe")
    eng = ContinuousServingEngine(bundle, max_batch=3, max_len=48,
                                  buckets=(16, 32), track_traffic=True)
    r = np.random.default_rng(1)
    with mesh:
        warm_s = eng.warmup(params)
        n0 = eng.compile_count
        assert n0 > 0 and eng.compile_s >= warm_s * 0.5
        # mixed lengths spanning both buckets, several admission rounds
        for i in range(7):
            eng.submit(r.integers(0, cfg.vocab, (5 + 4 * i,)), max_new=3)
        eng.run(params)
        assert eng.compile_count == n0
        # a second burst reuses everything too
        for i in range(3):
            eng.submit(r.integers(0, cfg.vocab, (30,)), max_new=2)
        eng.run(params)
    assert eng.compile_count == n0
    assert len(eng.finished) == 10


def test_continuous_first_ttft_within_factor_of_steady_state():
    """Regression for the TTFT-includes-compile bug: after warmup the FIRST
    request's TTFT must sit within a small factor of steady-state (compile
    is orders of magnitude above a single prefill, so a leak is loud)."""
    bundle, params, mesh, cfg = _bundle("dense")
    eng = ContinuousServingEngine(bundle, max_batch=2, max_len=48,
                                  buckets=(16,))
    r = np.random.default_rng(2)
    ttfts = []
    with mesh:
        eng.warmup(params)
        for _ in range(6):
            eng.submit(r.integers(0, cfg.vocab, (16,)), max_new=2)
            done = eng.run(params)
            ttfts.append(done[0].ttft_s)
    assert ttfts[0] <= 5 * np.median(ttfts[1:])


def test_waved_first_ttft_within_factor_of_steady_state():
    bundle, params, mesh, cfg = _bundle("dense")
    eng = ServingEngine(bundle, max_batch=1, max_len=48, buckets=(16,))
    r = np.random.default_rng(2)
    ttfts = []
    with mesh:
        eng.warmup(params)
        for _ in range(6):
            eng.submit(r.integers(0, cfg.vocab, (16,)), max_new=2)
            eng.run_wave(params)
            ttfts.append(eng.finished[-1].ttft_s)
    assert ttfts[0] <= 5 * np.median(ttfts[1:])


def test_continuous_eos_mid_decode_retires_and_refills():
    """eos mid-decode retires the slot early and the freed slot is refilled
    by a queued request; every stream equals the eos-free baseline truncated
    at its first eos (greedy decoding is deterministic)."""
    bundle, params, mesh, cfg = _bundle("dense")
    r = np.random.default_rng(3)
    prompts = [r.integers(0, cfg.vocab, (16,)) for _ in range(4)]

    def run(eos_id):
        eng = ContinuousServingEngine(bundle, max_batch=2, max_len=48,
                                      buckets=(16,), eos_id=eos_id)
        with mesh:
            eng.warmup(params)
            for p in prompts:
                eng.submit(p, max_new=6)
            eng.run(params)
        return {q.rid: q.output for q in eng.finished}

    base = run(eos_id=None)
    # an eos hitting request 0 mid-stream (not first, not last token)
    eos = base[0][2]
    cut = run(eos_id=eos)
    assert len(cut) == 4                       # freed slots were refilled
    assert len(cut[0]) == 3 and cut[0][-1] == eos
    for rid, full in base.items():
        idx = full.index(eos) if eos in full else len(full) - 1
        assert cut[rid] == full[:idx + 1]


@pytest.mark.parametrize("family", ["moe", "moe_ffn", "moe_tx"])
def test_continuous_matches_batch1_oracle(family):
    """Engine-harness conformance: per-slot admission must reproduce the
    batch-1 greedy reference streams exactly.  Prompts sit exactly on bucket
    boundaries (left-pad slots are attended by design, so parity is defined
    on-bucket; an admission chunk mixing buckets would left-pad the shorter
    prompt differently)."""
    bundle, params, mesh, cfg = _bundle(family)
    r = np.random.default_rng(4)
    # ordered so each admission chunk (<= 2 rows) is bucket-homogeneous
    lens = (16, 16, 32, 32)
    prompts = [r.integers(0, cfg.vocab, (n,)) for n in lens]
    ref = serving_stream_oracle(bundle, params, mesh, prompts, max_new=4,
                                buckets=(16, 32), max_len=48)

    eng = ContinuousServingEngine(bundle, max_batch=2, max_len=48,
                                  buckets=(16, 32),
                                  track_traffic=True)
    with mesh:
        eng.warmup(params)
        for p in prompts:
            eng.submit(p, max_new=4)
        eng.run(params)
    got = {q.rid: q.output for q in eng.finished}
    assert [got[i] for i in range(4)] == ref
    # traffic stats stream per ADMISSION, not per wave: >= 2 admissions here
    assert len(eng.wave_loads) >= 2
    for w in eng.wave_loads:
        assert w["expert_tokens"].sum() > 0 and w["lane_imbalance"] >= 1.0


def _op_names(exe) -> list[str]:
    import re
    return re.findall(r'op_name="([^"]*)"', exe.as_text())


def test_executables_are_named_and_carry_the_model_scopes():
    """The prefill, decode and insert executables are the modules
    ``jit_serve_*`` of a profile, and their ops carry the model's named
    scopes in their ``op_name`` metadata (what the trace reduction reads)."""
    bundle, params, mesh, cfg = _bundle("moe")
    eng = ContinuousServingEngine(bundle, max_batch=2, max_len=48,
                                  buckets=(16,))
    with mesh:
        eng.warmup(params)
    exes = {"prefill": eng._prefill_exec[(eng.admit_chunk, 16)],
            "decode": eng._decode_exec[2], "insert": eng._insert_exec}
    for name, exe in exes.items():
        assert exe.as_text().startswith(f"HloModule jit_serve_{name},")
    pre = "\n".join(_op_names(exes["prefill"]))
    for scope in ("layers/while/body", "attention", "moe.route",
                  "moe.dispatch", "moe.expert_ffn", "moe.combine", "lm_head"):
        assert f"/{scope}/" in pre, scope
    dec = _op_names(exes["decode"])
    assert any("/layers/" in n and "/attention/" in n for n in dec)
    for scope in ("moe.route", "moe.expert_ffn"):
        assert any(f"/moe.decode/" in n and f"/{scope}/" in n
                   for n in dec), scope
    assert any("/lm_head/" in n for n in dec)
    # prefill's shuffle scopes sit inside the layer scan
    assert all("/layers/" in n for n in pre.splitlines()
               if "/moe.dispatch/" in n)


def test_profile_of_two_steps_holds_the_engine_spans(tmp_path):
    """Two requests through a moe engine, two steps under the profiler: the
    trace holds the engine's spans with their arguments."""
    import glob
    from jax.profiler import ProfileData
    bundle, params, mesh, cfg = _bundle("moe")
    eng = ContinuousServingEngine(bundle, max_batch=2, max_len=48,
                                  buckets=(16,))
    r = np.random.default_rng(5)
    with mesh:
        eng.warmup(params)
        for _ in range(2):
            eng.submit(r.integers(0, cfg.vocab, (16,)), max_new=4)
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.step(params)
            eng.step(params)
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                events.setdefault(e.name, []).append(dict(e.stats))
    assert len(events["engine.step"]) == 2
    assert len(events["engine.decode"]) == 2
    for name in ("engine.admit", "engine.insert", "engine.wait",
                 "engine.retire"):
        assert name in events, name
    # one admit chunk (one row on this mesh) per request, in the first step
    pre = events["engine.prefill"]
    assert [p["rids"] for p in pre] == ["[0]", "[1]"]
    assert all(p["rows"] == 1 and p["bucket"] == 16 for p in pre)
    assert [d["rows"] for d in events["engine.decode"]] == [2, 2]
    # one wait per prefill and one per decode
    assert len(events["engine.wait"]) == 4


def test_engine_counters_add_up():
    """``wait_s`` is time inside steps, so it never exceeds ``step_s``; the
    decode and prefill times hold their waits."""
    bundle, params, mesh, cfg = _bundle("dense")
    eng = ContinuousServingEngine(bundle, max_batch=2, max_len=48,
                                  buckets=(16,))
    r = np.random.default_rng(6)
    with mesh:
        eng.warmup(params)
        assert eng.steps == 0 and eng.wait_s == 0.0
        for _ in range(3):
            eng.submit(r.integers(0, cfg.vocab, (16,)), max_new=3)
        eng.run(params)
    assert eng.steps >= 3 and eng.prefill_calls == 3   # one row a chunk
    assert 0 < eng.wait_s <= eng.step_s
    assert eng.decode_s + eng.prefill_s <= eng.step_s
    assert eng.wait_s <= eng.decode_s + eng.prefill_s
    for q in eng.finished:
        assert q.submitted_at <= q.admitted_at
        assert q.admitted_at - q.submitted_at <= q.ttft_s
    st = eng.stats()
    for k in ("steps", "step_s", "wait_s", "prefill_calls", "prefill_s"):
        assert st[k] == getattr(eng, k), k
    # the TTFT split an operator reads: queue wait, then the prefill
    waits = sorted(q.admitted_at - q.submitted_at for q in eng.finished)
    assert waits[0] <= st["p50_queue_wait_s"] <= st["p95_queue_wait_s"]
    assert st["p95_queue_wait_s"] <= waits[-1]
    assert st["mean_prefill_s"] == pytest.approx(eng.prefill_s / 3)


def test_decode_expert_weight_views_belong_to_the_layer_scan():
    """The decode's per-layer slice of the stacked expert weights, and the
    block's view of this lane's experts, sit under the layer scan and no
    finer scope: on a TPU the copy they can cost is ``layers.scan_io`` in
    a trace (a fusion takes its root op's op_name), not the expert FFN."""
    import re
    import jax.numpy as jnp
    from bench import scopes as S
    bundle, params, mesh, cfg = _bundle("moe")
    eng = ContinuousServingEngine(bundle, max_batch=2, max_len=48,
                                  buckets=(16,))

    def serve_decode(p, st, t):
        return bundle.decode_step(p, st, t, eng.max_len)
    with mesh:
        eng._ensure_pool()
        text = jax.jit(serve_decode).lower(
            params, eng._state, jnp.zeros((2,), jnp.int32)).as_text(
                dialect="hlo", debug_info=True)
    e, d, f = cfg.moe.n_experts, cfg.d_model, cfg.moe.d_ff_expert
    views = {f"bf16[{','.join(map(str, lead + w))}]"
             for lead in ((), (1,), (1, 1)) for w in ((e, d, f), (e, f, d))}
    # (result shape without its layout, opcode, op_name) of every op
    ops = [(m.group(1), m.group(2), name)
           for rest, name in re.findall(
               r'^\s*(?:ROOT )?%?\S+ = (.*)op_name="([^"]*)"', text, re.M)
           for m in [re.match(r"([a-z]\w*\[[\d,]*\])\S* ([a-z][\w-]*)\(",
                              rest)] if m]
    picked = [(op, name) for dims, op, name in ops if dims in views
              and op in ("dynamic-slice", "reshape", "slice", "bitcast")]
    # a layer's slice of w1, w3, w2, and the block's view of each
    assert sum(op == "dynamic-slice" for op, _ in picked) >= 3
    assert sum(op == "reshape" for op, _ in picked) >= 6
    finer = set(S.SCOPES) - {"layers"}
    for op, name in picked:
        assert not finer & set(name.split("/")), (op, name)
    assert all("/layers/" in name for op, name in picked
               if op == "dynamic-slice")
