"""Serving driver: the program's ContinuousServingEngine at a
configuration's sizes, fed by a traffic mix.

One run: weights from the seed on the device, the engine's executables
warmed (every bucket the mix can send), a lead-in of the mix's own traffic,
then ``seconds`` of measurement.  Every token is stamped on the host as it
arrives; the engine's counters are read at the window's edges.  After the
window, a sample of the requests it finished goes through the plain
reference (``bench/reference.py``), and ``correct`` says whether the
configuration's number (the mean gap of a served token's reference logit
below the reference's best) lies within its limit.  With ``control`` the
reference in a lower precision is put in the program's place for that
comparison: the control, which has to come out not correct.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time

import numpy as np

from bench import flops as F
from bench import reference, trace as trace_lib, traffic, weights

TRACE_S = 3.0          # traced part of the window (--trace 1)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def build(config: dict, mix: dict, seed: int):
    """The program under test: bundle, weights, engine (not yet warm)."""
    import jax
    import jax.numpy as jnp
    from repro.compat import make_mesh
    from repro.configs import get_arch
    from repro.models import zoo
    from repro.models.lm import make_context
    from repro.parallel.sharding import param_specs, shardings
    from repro.serving.engine import ContinuousServingEngine

    model, prog = config["model"], config["program"]
    arch = as_program_arch(get_arch(prog["arch"]), model)
    mesh = make_mesh(tuple(prog["mesh"].values()), tuple(prog["mesh"]))
    ctx = make_context(arch, mesh, multi_pod=False, engine=prog["engine"],
                       capacity_factor=prog["capacity_factor"],
                       node_size=prog["node_size"])
    bundle = zoo.build(arch, ctx)
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
        jax.eval_shape(bundle.init, jax.random.PRNGKey(0)))
    specs = param_specs(shapes, multi_pod=False,
                        model_size=mesh.shape["model"],
                        fsdp_experts=ctx.fsdp_experts)
    make = jax.jit(lambda k: weights.build_tree(k, shapes, arch.n_layers),
                   out_shardings=shardings(specs, mesh))
    params = make(weights.root_key(seed))
    jax.block_until_ready(params)
    eng = ContinuousServingEngine(
        bundle, max_batch=prog["slots"], max_len=traffic.max_len(mix),
        buckets=tuple(traffic.lengths_of(mix)))
    return mesh, params, eng


def as_program_arch(base, model: dict):
    """The program's architecture ``base`` at the configuration's sizes:
    every width and depth comes from the configuration file."""
    moe = dataclasses.replace(
        base.moe, n_experts=model["num_experts"],
        top_k=model["num_experts_per_tok"],
        d_ff_expert=model["moe_intermediate_size"],
        norm_topk=model["norm_topk_prob"])
    return dataclasses.replace(
        base, n_layers=model["num_hidden_layers"],
        d_model=model["hidden_size"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        vocab=model["vocab_size"], qk_norm=model["qk_norm"],
        rope_theta=model["rope_theta"], moe=moe)


class Clock:
    """Host-side stamps of every token, keyed by request id."""

    def __init__(self):
        self.times: dict[int, list[float]] = {}
        self.due: dict[int, float] = {}      # absolute perf_counter time
        self.req = {}

    def submit(self, eng, r: traffic.Req, due: float) -> int:
        rid = eng.submit(r.prompt, max_new=r.max_new)
        self.due[rid] = due
        self.times[rid] = []
        return rid

    def stamp(self, eng, retired, now: float) -> list[int]:
        """Stamp the tokens a step produced; returns the prompt lengths of
        the requests it admitted (their first token came from a prefill,
        stamped when the engine read it)."""
        admitted = []
        for q in [q for q in eng.slots if q is not None] + list(retired):
            ts = self.times[q.rid]
            self.req[q.rid] = q
            new = len(q.output) - len(ts)
            if new > 0 and not ts:
                ts.append(q.submitted_at + q.ttft_s)
                admitted.append(len(q.prompt))
                new -= 1
            ts.extend([now] * new)
        return admitted


def drive(eng, params, mix, seed, seconds, trace_dir=None):
    """Lead-in, then the window; returns the run's raw record."""
    import jax
    vocab = eng.bundle.cfg.vocab
    clock = Clock()
    steps = []                      # (prompt lengths admitted, rows decoded)
    snap = {}
    tr = {"on": False}

    if mix["kind"] == "open_poisson":
        sched = traffic.schedule(mix, seed, seconds, vocab)
        feed = None
    else:
        sched = []
        feed = traffic.backlog(mix, seed, vocab)
    start = time.perf_counter()
    t_open = start + mix["lead_s"]
    t_close = t_open + seconds
    nxt = 0
    rid_of: dict[int, int] = {}     # schedule index -> request id

    def step():
        occupied = sum(q is not None for q in eng.slots)
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            retired = eng.step(params)
        admitted = clock.stamp(eng, retired, time.perf_counter())
        # the pool decodes once per step over every slot then occupied
        steps.append((admitted, occupied + len(admitted)))

    while True:
        now = time.perf_counter()
        if not snap and now >= t_open:
            snap.update(compiles=eng.compile_count, decode_s=eng.decode_s,
                        decode_steps=eng.decode_steps, occ=len(eng.occupancy),
                        occupied=sum(q is not None for q in eng.slots))
            if trace_dir is not None:
                trace_lib.start(trace_dir)
                tr.update(on=True, t0=time.perf_counter(), i0=len(steps))
        if tr["on"] and now >= tr["t0"] + TRACE_S:
            trace_lib.stop()
            tr.update(on=False, i1=len(steps))
        if now >= t_close:
            break
        if feed is not None:
            while len(eng.queue) < mix["queue_depth"]:
                clock.submit(eng, next(feed), time.perf_counter())
        else:
            while nxt < len(sched) and start + sched[nxt].due <= now:
                rid_of[nxt] = clock.submit(eng, sched[nxt],
                                           start + sched[nxt].due)
                nxt += 1
        if eng.pending():
            step()
        else:
            due = start + sched[nxt].due if nxt < len(sched) else t_close
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(max(0.0, min(due, t_close) - now))
    if tr["on"]:
        trace_lib.stop()
        tr.update(on=False, i1=len(steps))
    snap_close = dict(compiles=eng.compile_count, decode_s=eng.decode_s,
                      decode_steps=eng.decode_steps, occ=len(eng.occupancy),
                      queued=len(eng.queue))
    return dict(clock=clock, steps=steps, start=start, t_open=t_open,
                t_close=t_close, snap=snap, snap_close=snap_close,
                sched=sched, rid_of=rid_of, trace=tr)


def summarize(run, eng, model: dict, seconds: float) -> dict:
    """The record the metric readers read."""
    clock = run["clock"]
    t_open, t_close = run["t_open"], run["t_close"]
    ttft, lateness = [], []
    censored = 0
    for i, r in enumerate(run["sched"]):
        rid = run["rid_of"].get(i)
        if rid is not None:
            q = clock.req.get(rid) or next(
                (x for x in eng.queue if x.rid == rid), None)
            lateness.append(q.submitted_at - clock.due[rid])
        if r.phase != "window":
            continue
        due = run["start"] + r.due
        ts = clock.times.get(rid) if rid is not None else None
        if ts and ts[0] < t_close:
            ttft.append(ts[0] - due)
        else:
            ttft.append(t_close - due)
            censored += 1
    itl = []
    prompt_tokens = out_tokens = admitted = 0
    model_flops = 0.0
    for rid, ts in clock.times.items():
        q = clock.req.get(rid)
        n = len(q.prompt) if q is not None else 0
        for j, t in enumerate(ts):
            if not t_open <= t < t_close:
                continue
            out_tokens += 1
            if j == 0:
                prompt_tokens += n
                admitted += 1
                model_flops += F.prefill_flops(model, n)
            else:
                model_flops += F.decode_flops(model, n + j)
                itl.append(t - ts[j - 1])
    s0, s1 = run["snap"], run["snap_close"]
    return {
        "window_s": seconds,
        "open_loop": bool(run["sched"]),
        "ttft_s": ttft, "itl_s": itl, "censored": censored,
        "attempted": len(ttft) if run["sched"] else admitted,
        "prompt_tokens": prompt_tokens, "output_tokens": out_tokens,
        "model_flops": model_flops,
        "decode_s": s1["decode_s"] - s0["decode_s"],
        "decode_steps": s1["decode_steps"] - s0["decode_steps"],
        "occupancy": eng.occupancy[s0["occ"]:s1["occ"]],
        "compiles_in_window": s1["compiles"] - s0["compiles"],
        "queued_at_close": s1["queued"],
        "occupied_at_open": s0["occupied"],
        "finished_in_window": sum(
            1 for rid, ts in clock.times.items()
            if ts and t_open <= ts[-1] < t_close and clock.req[rid].done),
        "lateness_s": lateness,
    }


def trace_work(run):
    """The model calls of the traced steps: ("prefill", prompt length) per
    admitted request and ("decode", rows occupied) per pool decode."""
    tr = run["trace"]
    if "i1" not in tr:
        return None
    calls = []
    for admitted, occupied in run["steps"][tr["i0"]:tr["i1"]]:
        calls.extend(("prefill", n) for n in admitted)
        if occupied:
            calls.append(("decode", occupied))
    return calls


def sample(eng_finished, clock, t_open, t_close, n: int, seed: int):
    """Requests finished in the window: the longest, then others drawn
    from the seed."""
    done = [q for q in eng_finished
            if clock.times.get(q.rid) and
            t_open <= clock.times[q.rid][-1] < t_close]
    if not done:
        return []
    done.sort(key=lambda q: q.rid)
    longest = max(done, key=lambda q: len(q.prompt) + len(q.output))
    rest = [q for q in done if q is not longest]
    rng = np.random.default_rng(seed ^ 0x5EED)
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gaps(config: dict, seed: int, reqs, seq_len: int,
                   max_out: int, control=None):
    """The reference's readings of each sampled request's served tokens
    (or, with ``control``, of the tokens the control puts first)."""
    b = len(reqs)
    plen = np.array([len(q.prompt) for q in reqs], np.int32)
    tokens = np.zeros((b, seq_len), np.int32)
    rows = np.zeros((b, max_out), np.int32)
    served = np.zeros((b, max_out), np.int32)
    valid = np.zeros((b, max_out), bool)
    for i, q in enumerate(reqs):
        n, m = len(q.prompt), len(q.output)
        tokens[i, :n] = q.prompt
        tokens[i, n:n + m] = q.output
        rows[i, :m] = np.arange(n - 1, n - 1 + m)
        served[i, :m] = q.output
        valid[i, :m] = True
    return reference.served_gaps(config["model"], seed, tokens, rows, served,
                                 valid, plen, control=control)


def run(spec: dict, seed: int, seconds: float, trace: bool, t0: float,
        device: dict, peaks: dict, trace_dir=None, control=None,
        cache: bool = True, check: bool = True):
    import jax
    if cache:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    config = spec["config"]
    mix = traffic.load_mix(spec["cell"]["traffic"],
                           spec["root"] / "bench" / "traffic")
    model = config["model"]
    mesh, params, eng = build(config, mix, seed)
    with mesh:
        warm_s = eng.warmup(params)
        compiles_warm = eng.compile_count
        runrec = drive(eng, params, mix, seed, seconds,
                       trace_dir=trace_dir if trace else None)
        setup_s = runrec["t_open"] - t0
        rec = summarize(runrec, eng, model, seconds)
        rec["setup_s"] = setup_s
        rec["peak"] = peaks[device["kind"]]
        rec["model"] = model
        rec["chips"] = device["count"]
        mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in jax.devices())
        chk = config["check"]
        picked = sample(eng.finished, runrec["clock"], runrec["t_open"],
                        runrec["t_close"],
                        mix.get("check_requests", chk["requests"]), seed)
    log(f"warmup {warm_s:.3f} s, {compiles_warm} executables; setup "
        f"{setup_s:.3f} s; lead-in {mix['lead_s']} s")
    if trace:
        rec["trace"] = trace_lib.reduce_dir(trace_dir)
        rec["trace_calls"] = trace_work(runrec)
    if rec["ttft_s"]:
        top = sorted(rec["ttft_s"])[-8:]
        log("ttft ms p50 {:.3f} p90 {:.3f} p95 {:.3f}; largest {}".format(
            *np.percentile(rec["ttft_s"], [50, 90, 95]) * 1e3,
            [round(t * 1e3, 3) for t in top]))
        log(f"slots occupied at the window's open {rec['occupied_at_open']}")
    lat = rec["lateness_s"]
    if lat:
        log(f"generator lateness p50 {np.percentile(lat, 50):.6f} s, p95 "
            f"{np.percentile(lat, 95):.6f} s, max {max(lat):.6f} s")
    log(f"window {seconds} s: {rec['attempted']} attempted, "
        f"{rec['censored']} without a first token, {rec['prompt_tokens']} "
        f"prompt + {rec['output_tokens']} output tokens, "
        f"{rec['decode_steps']} decode steps, {rec['compiles_in_window']} "
        f"compiles")
    # the program's state goes before the reference runs
    del params, eng, runrec
    gc.collect()
    if not check:
        return rec, dict(device, memory_peak_bytes=int(mem)), False, [], {}
    t_ref = time.perf_counter()
    number, limit = chk["number"], chk["limit"]
    if picked:
        seq_len = -(-traffic.max_len(mix) // 128) * 128
        stats = reference_gaps(config, seed, picked, seq_len,
                               int(mix["output"]["max"]), control=control)
        served = sum(len(q.output) for q in picked)
        value = float(np.max(stats[number]))
    else:
        stats, served, value = {}, 0, math.inf
    log(f"reference over {len(picked)} requests, {served} served tokens, "
        f"{time.perf_counter() - t_ref:.3f} s")
    if control is not None:
        log(f"control: the reference in {control} is put in the program's "
            "place; served.* reads its tokens, program.* the program's")
    for k, v in stats.items():
        v = [float(x) for x in v] if np.ndim(v) else float(v)
        log(f"  {k} {v}")
    checks = [(number, value, limit)]
    rec["served_checked"] = served
    correct = bool(served > 0 and limit is not None and value <= limit)
    dev = dict(device, memory_peak_bytes=int(mem))
    return rec, dev, correct, checks, stats
