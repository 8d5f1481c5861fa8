"""Serving engines: continuous per-slot batching (+ a waved compat mode)
over the zoo's prefill/decode steps.

Two admission disciplines share one base (queue, prompt-length bucketing,
AOT-compiled executables, traffic stats, metrics):

  * :class:`ContinuousServingEngine` — the production path.  A fixed pool of
    ``max_batch`` decode *slots* with per-row position counters in the
    ``DecodeState`` (``models/lm.decode_step`` RoPE-rotates, cache-writes and
    masks each row at its own position).  A queued request is prefilled at a
    bucketed prompt length and *inserted* into a free slot while the other
    slots keep decoding; a slot retires on eos/max_new and is refilled on the
    next step — one straggler never holds the pool.  Prompt lengths are
    padded to a small set of buckets whose prefill executables are
    AOT-compiled (``jax.jit(...).lower().compile()``), so steady-state
    admission never recompiles (``compile_count`` stays flat after
    ``warmup``).

  * :class:`ServingEngine` — the original *waved* engine, kept as a thin
    compatibility mode: pending requests are padded to a common (bucketed)
    prompt length and prefilled as one batch, then decoded lock-step until
    every member finishes.  One straggler holds every slot — exactly the
    behaviour ``bench_serving`` quantifies against the continuous engine.

The FUSCO engines sit in the prefill path of both — the paper's TTFT metric.
TTFT excludes compile time in both engines: executables are fetched (and, if
missing, compiled — charged to ``compile_s``/``compile_count``) *before* the
timed prefill call, so the first request's TTFT is within noise of
steady-state (regression-tested).

Metrics: TTFT per request (p50/p95/p99 in ``stats()``), decode tok/s, queue
latency, slot occupancy, and the continuous engine's host counters
(``step_s``, ``wait_s``, ``prefill_s``) that match its profiler spans
(``engine.step`` > ``engine.admit`` > ``engine.prefill`` /
``engine.insert``, ``engine.decode``, ``engine.wait``, ``engine.retire``;
recorded only while a profiler runs) — plus, for MoE models with
``track_traffic=True``, per-admission expert-load statistics from the
online traffic subsystem (``core/traffic.py``): the prefill threads an EMA
``TrafficState`` through the MoE islands (``moe`` per-layer,
``moe_ffn``/``moe_tx`` per stream block), and each admission's raw routing
counts are reported as max/mean lane load and hot-expert share.  Under continuous admission this stream is
*live*: stats update per admitted request rather than per wave, which is
what lets a between-decodes re-layout policy (LAER-MoE style) act on them.

Interleave lanes: when the bundle is a ``moe_ffn``/``moe_tx`` stack with
``ModelContext.moe_interleave == K``, prefill rows ARE the micro-batch lanes
of the interleaved layer stream.  The continuous engine draws the K lanes
from the queued requests of one admission chunk (``K × data-shards`` rows
per prefill-insert) instead of padding one whole wave; the waved engine
still pads each wave's batch up to the lane multiple.  Pad rows carry pad
tokens, are excluded from results and (via the validity mask) from traffic.

Traffic validity: every prefill builds a (rows, S) pad mask (False on
left-pad slots and on whole pad rows) and threads it into
``traffic.observe`` — pad positions are still routed (static shapes) but
contribute nothing to the EMA or the load snapshots.  Pad-invariance is
asserted in ``tests/test_serving.py``.  Note bucketing pads more positions
than exact-length waves did; pad tokens still consume engine capacity, so
serving configs should keep an ample ``capacity_factor`` (the masks keep the
*stats* exact either way).
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import commplan, relayout, traffic as traffic_lib
from repro.models import lm

TRAFFIC_FAMILIES = ("moe", "moe_ffn", "moe_tx")


def _uncommitted(tree):
    """Round-trip a small pytree through host memory so it comes back as
    plain (uncommitted) arrays.  AOT executables are strict about input
    shardings; values that cycle through them every call (the traffic EMA,
    the next-token ids) must present ONE stable sharding, and for KB-sized
    state the host round-trip is the cheapest way to pin it."""
    return jax.tree.map(lambda x: jnp.asarray(np.asarray(x)), tree)


def _avals_like(tree):
    """ShapeDtypeStructs carrying each leaf's sharding (accepts concrete
    arrays and already-sharded ShapeDtypeStructs)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        tree)


def greedy(out):
    """(logits, *rest) -> (greedy token ids, *rest), traced into the
    serving executables so sampling never runs (or compiles) eagerly."""
    logits, *rest = out
    with jax.named_scope("lm_head"):
        ids = jnp.argmax(logits, -1).astype(jnp.int32)
    return (ids, *rest)


def _same_shardings(a, b) -> bool:
    return jax.tree.all(jax.tree.map(lambda x, y: x == y, a, b))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray               # (S,) int32
    max_new: int
    submitted_at: float = 0.0
    admitted_at: Optional[float] = None   # left the queue for a slot
    ttft_s: Optional[float] = None
    output: list = dataclasses.field(default_factory=list)
    done: bool = False


def default_buckets(max_len: int, lo: int = 16) -> tuple[int, ...]:
    """Powers of two from ``lo`` up to (and always including) ``max_len``."""
    out, b = [], lo
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


class _ServingBase:
    """Shared machinery: queue, buckets, AOT executables, traffic, stats."""

    def __init__(self, bundle, *, max_batch: int = 8, max_len: int = 256,
                 eos_id: int | None = None, pad_id: int = 0,
                 track_traffic: bool = False,
                 buckets: tuple[int, ...] | None = None):
        self.bundle = bundle
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.buckets = tuple(sorted(buckets)) if buckets else \
            default_buckets(max_len)
        self.queue: deque[Request] = deque()
        self.finished: list[Request] = []
        self.wave_loads: list[dict] = []     # one entry per wave / admission
        self._next_id = 0
        # compile accounting: every executable build is counted and timed
        # here, NEVER inside a request's TTFT
        self.compile_count = 0
        self.compile_s = 0.0
        self._prefill_exec: dict = {}        # (rows, s) -> compiled
        self._decode_exec: dict = {}         # rows -> compiled
        # batch rows shard over the data axes, so every prefill batch must be
        # a multiple of the data-shard count; moe_ffn/moe_tx interleaved
        # streams additionally split the per-shard rows into K lanes
        self.interleave = (getattr(bundle.ctx, "moe_interleave", 1)
                           if bundle.ctx.cfg.family in ("moe_ffn", "moe_tx")
                           else 1)
        dsz = 1
        for ax in bundle.ctx.data_axes:
            dsz *= dict(bundle.ctx.mesh.shape)[ax]
        self._wave_mult = self.interleave * dsz
        self.traffic = None
        if track_traffic:
            ctx = bundle.ctx
            if ctx.cfg.moe is None or ctx.cfg.family not in TRAFFIC_FAMILIES:
                raise ValueError(
                    "track_traffic requires a moe/moe_ffn/moe_tx-family "
                    f"bundle, got {ctx.cfg.family!r}")
            self.traffic = traffic_lib.init_traffic_state(
                ctx.cfg.moe.n_experts, ctx.placement.ep,
                n_layers=ctx.cfg.n_layers)

    # ------------------------------------------------------------- queue ----

    def submit(self, prompt: np.ndarray, max_new: int = 16) -> int:
        prompt = np.asarray(prompt, np.int32)
        if len(prompt) > self.buckets[-1]:
            raise ValueError(f"prompt length {len(prompt)} exceeds the "
                             f"largest bucket {self.buckets[-1]}")
        rid = self._next_id
        self._next_id += 1
        self.queue.append(Request(rid, prompt, max_new,
                                  submitted_at=time.perf_counter()))
        return rid

    def bucket_of(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds bucket {self.buckets[-1]}")

    # ------------------------------------------- AOT-compiled executables ---

    def _prefill_callable(self) -> Callable:
        """Prefill + greedy sampling in one executable (``jit_serve_prefill``
        in a profile): returns the first token ids, never the (rows, vocab)
        logits."""
        bundle, max_len = self.bundle, self.max_len
        if self.traffic is not None:
            def serve_prefill(p, toks, tr, m):
                return greedy(bundle.prefill(p, {"tokens": toks}, max_len,
                                             traffic=tr, traffic_mask=m))
        else:
            def serve_prefill(p, toks):
                return greedy(bundle.prefill(p, {"tokens": toks}, max_len))
        return serve_prefill

    def _prefill_avals(self, rows: int, s: int):
        toks = jax.ShapeDtypeStruct((rows, s), jnp.int32)
        if self.traffic is not None:
            return (toks, self.traffic, jax.ShapeDtypeStruct((rows, s),
                                                             jnp.bool_))
        return (toks,)

    def get_prefill(self, params, rows: int, s: int):
        """AOT prefill executable for a (rows × bucket-s) token batch;
        compiled on first request for the shape (or by ``warmup``)."""
        key = (rows, s)
        exe = self._prefill_exec.get(key)
        if exe is None:
            t0 = time.perf_counter()
            exe = (jax.jit(self._prefill_callable())
                   .lower(params, *self._prefill_avals(rows, s)).compile())
            self._prefill_exec[key] = exe
            self.compile_count += 1
            self.compile_s += time.perf_counter() - t0
        return exe

    def get_decode(self, params, state, rows: int):
        """AOT one-token decode executable for a ``rows``-slot state.
        ``state`` may be concrete or a sharding-carrying ShapeDtypeStruct
        pytree; the executable is pinned so its output state sharding equals
        its input's — the state cycles through it every token, and a drift
        would reject the second call."""
        exe = self._decode_exec.get(rows)
        if exe is None:
            t0 = time.perf_counter()

            def serve_decode(p, st, t):
                return greedy(self.bundle.decode_step(p, st, t, self.max_len))
            st_avals = _avals_like(state)
            tok = jax.ShapeDtypeStruct((rows,), jnp.int32)
            exe = jax.jit(serve_decode).lower(params, st_avals, tok).compile()
            self.compile_count += 1
            out_tok, out_st = exe.output_shardings
            in_st = jax.tree.map(lambda x: x.sharding, st_avals)
            if not _same_shardings(out_st, in_st):
                exe = (jax.jit(serve_decode, out_shardings=(out_tok, in_st))
                       .lower(params, st_avals, tok).compile())
                self.compile_count += 1
            self._decode_exec[rows] = exe
            self.compile_s += time.perf_counter() - t0
        return exe

    def _prefill_state_avals(self, params, rows: int, s: int):
        """Avals of the prefill's output DecodeState, carrying the compiled
        prefill executable's REAL output shardings (no prefill run — traffic
        state stays untouched)."""
        out = jax.eval_shape(self._prefill_callable(), params,
                             *self._prefill_avals(rows, s))
        out_sh = self._prefill_exec[(rows, s)].output_shardings
        return jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            out[1], out_sh[1])

    def _warm_decode(self, params, rows: int, s: int):
        if rows not in self._decode_exec:
            self.get_decode(params, self._prefill_state_avals(params, rows, s),
                            rows)

    def _dry_prefill(self, params, rows: int, s: int):
        """Compile the (rows × s) prefill and run it once on pad tokens,
        results discarded (traffic state untouched).  An executable's first
        run pays one-time costs of its own (tens of ms on XLA:CPU) that
        must not land in the first request's TTFT.  Returns the state."""
        exe = self.get_prefill(params, rows, s)
        toks = jnp.asarray(np.full((rows, s), self.pad_id, np.int32))
        args = (toks,) if self.traffic is None else (
            toks, self.traffic, jnp.asarray(np.zeros((rows, s), bool)))
        first, state, *_ = exe(params, *args)
        jax.block_until_ready(first)
        return state

    # ---------------------------------------------------- traffic + stats ---

    def _record_load(self):
        """Per-admission (continuous) / per-wave (waved) expert-load snapshot
        from the raw (non-EMA) counts of the prefill, summed over layers."""
        counts = np.asarray(self.traffic.last_expert_count).sum(axis=0)
        lanes = relayout.lane_loads(counts, self.bundle.ctx.placement)
        tot = max(float(counts.sum()), 1e-9)
        self.wave_loads.append({
            "expert_tokens": counts,
            "max_lane_load": float(lanes.max()),
            "mean_lane_load": float(lanes.mean()),
            "lane_imbalance": float(lanes.max() / max(lanes.mean(), 1e-9)),
            "top_expert_share": float(counts.max() / tot),
        })

    def stats(self) -> dict:
        done = [r for r in self.finished if r.ttft_s is not None]
        if not done:
            return {}
        ttfts = [r.ttft_s for r in done]
        out = {
            "requests": len(done),
            "mean_ttft_s": float(np.mean(ttfts)),
            "p50_ttft_s": float(np.percentile(ttfts, 50)),
            "p95_ttft_s": float(np.percentile(ttfts, 95)),
            "p99_ttft_s": float(np.percentile(ttfts, 99)),
            "mean_tokens": float(np.mean([len(r.output) for r in done])),
            "compile_s": self.compile_s,
            "compile_count": self.compile_count,
        }
        if self.wave_loads:
            out["waves"] = len(self.wave_loads)
            out["mean_lane_imbalance"] = float(
                np.mean([w["lane_imbalance"] for w in self.wave_loads]))
            out["max_lane_imbalance"] = float(
                np.max([w["lane_imbalance"] for w in self.wave_loads]))
            out["mean_top_expert_share"] = float(
                np.mean([w["top_expert_share"] for w in self.wave_loads]))
        if self.traffic is not None:
            ctx = self.bundle.ctx
            decisions = commplan.plan_paths(
                self.traffic, ctx.placement,
                row_bytes=ctx.cfg.d_model * jnp.dtype(ctx.compute_dtype).itemsize,
                costs=commplan.LinkCosts.from_dcomm(ctx.dcfg),
                dedup=ctx.dcfg.dedup, default=ctx.dcfg.engine)
            out["comm_path"] = commplan.summarize_decisions(decisions)
            out["comm_path"]["dedup"] = commplan.dedup_savings(
                self.traffic, ctx.placement)
        return out


class ServingEngine(_ServingBase):
    """Waved (lock-step) admission — the compatibility mode.

    ``run_wave`` drains up to ``max_batch`` queued requests, pads them to a
    common bucketed prompt length, prefills them as one batch and decodes
    lock-step until every member finishes.  Kept so existing tests/benches
    (and the straggler baseline in ``bench_serving``) keep running; new
    callers want :class:`ContinuousServingEngine`.
    """

    def warmup(self, params) -> float:
        """Pre-compile the full-wave prefill executable per bucket plus the
        decode step, and run each once; returns the seconds spent.  Waves
        smaller than ``max_batch`` still compile lazily on first occurrence
        (also outside TTFT)."""
        t0 = time.perf_counter()
        rows = -(-self.max_batch // self._wave_mult) * self._wave_mult
        states = [self._dry_prefill(params, rows, s) for s in self.buckets]
        self._warm_decode(params, rows, self.buckets[0])
        tok = jnp.asarray(np.full((rows,), self.pad_id, np.int32))
        jax.block_until_ready(self._decode_exec[rows](params, states[0], tok))
        return time.perf_counter() - t0

    def _form_wave(self) -> list[Request]:
        wave = []
        while self.queue and len(wave) < self.max_batch:
            wave.append(self.queue.popleft())
        return wave

    def run_wave(self, params) -> list[Request]:
        """Prefill + decode one wave to completion.  Returns finished reqs."""
        wave = self._form_wave()
        if not wave:
            return []
        s = self.bucket_of(max(len(r.prompt) for r in wave))
        b = len(wave)
        # pad the batch up to a multiple of (interleave lanes × data shards);
        # pad rows are full pad-token rows, sliced off every result below
        bp = -(-b // self._wave_mult) * self._wave_mult
        toks = np.full((bp, s), self.pad_id, np.int32)
        valid = np.zeros((bp, s), bool)      # False: left-pad slot / pad row
        for i, r in enumerate(wave):
            toks[i, s - len(r.prompt):] = r.prompt      # left-pad
            valid[i, s - len(r.prompt):] = True
        batch = jnp.asarray(toks)

        # fetch (and if needed compile) executables BEFORE the timed region:
        # compile goes to compile_s, never into a request's TTFT
        exe = self.get_prefill(params, bp, s)
        t0 = time.perf_counter()
        if self.traffic is not None:
            first, state, traffic = exe(params, batch, self.traffic,
                                        jnp.asarray(valid))
            self.traffic = _uncommitted(traffic)
            self._record_load()
        else:
            first, state = exe(params, batch)
        tok_np = np.asarray(first)
        end = time.perf_counter()
        for r in wave:
            r.ttft_s = end - r.submitted_at

        dec = self.get_decode(params, state, bp)
        live = np.ones(b, bool)
        steps = max(r.max_new for r in wave)
        for step in range(steps):
            for i, r in enumerate(wave):
                if not live[i]:
                    continue
                r.output.append(int(tok_np[i]))
                if (len(r.output) >= r.max_new or
                        (self.eos_id is not None and tok_np[i] == self.eos_id)):
                    live[i] = False
                    r.done = True
            if not live.any() or step == steps - 1:
                break
            tok, state = dec(params, state, jnp.asarray(tok_np))
            tok_np = np.asarray(tok)
        for r in wave:
            r.done = True
        self.finished.extend(wave)
        return wave


class ContinuousServingEngine(_ServingBase):
    """Per-slot continuous admission over a fixed pool of ``max_batch``
    decode slots (MaxText offline-inference style).

    ``step(params)`` = admit (prefill-insert queued requests into free
    slots) + one lock-step decode of the whole pool.  The pool
    ``DecodeState`` carries per-row position counters, so freshly admitted
    requests decode next to slots mid-way through theirs; free slots decode
    garbage that is dropped.  Retired slots (eos seen or ``max_new``
    reached) hand their request to the ``emit`` hook immediately — the
    async detokenize/emit path — and are refilled on the next step.

    Admission prefills exactly ``admit_chunk = interleave × data-shards``
    rows per call: for interleaved stream families the chunk's request rows
    ARE the K stream lanes (drawn from the queue, not from one padded
    wave).  Prompts are left-padded to the smallest bucket that fits the
    chunk; every (chunk × bucket) prefill executable is AOT-compiled, so
    steady-state admission never recompiles.
    """

    def __init__(self, bundle, *, max_batch: int = 8, max_len: int = 256,
                 eos_id: int | None = None, pad_id: int = 0,
                 track_traffic: bool = False,
                 buckets: tuple[int, ...] | None = None,
                 emit: Callable[[Request], None] | None = None):
        if bundle.ctx.cfg.family == "encdec":
            raise ValueError("continuous batching supports the LM families "
                             "only (encdec prefill takes frames)")
        super().__init__(bundle, max_batch=max_batch, max_len=max_len,
                         eos_id=eos_id, pad_id=pad_id,
                         track_traffic=track_traffic, buckets=buckets)
        if max_batch % self._wave_mult:
            raise ValueError(
                f"max_batch={max_batch} must be a multiple of interleave "
                f"lanes x data shards ({self._wave_mult}) — the pool decode "
                "shards rows over the data axes")
        self.emit = emit
        self.admit_chunk = self._wave_mult
        self.slots: list[Optional[Request]] = [None] * max_batch
        self.occupancy: list[float] = []     # per-step occupied fraction
        self.decode_steps = 0
        self.decode_tokens = 0
        self.decode_s = 0.0                  # decode launch to tokens on host
        # host clock of the engine.* profiler spans (no device sync added)
        self.steps = 0
        self.step_s = 0.0                    # whole step() calls
        self.wait_s = 0.0                    # blocked on a device result
        self.prefill_calls = 0
        self.prefill_s = 0.0                 # per admit chunk, incl. its wait
        self._tok = np.full((max_batch,), pad_id, np.int32)
        self._state = None                   # pool DecodeState, built lazily
        self._insert_exec = None

    # ------------------------------------------------------------- state ----

    def _ensure_pool(self):
        if self._state is None:
            ctx = self.bundle.ctx
            self._state = lm.init_decode_state(
                ctx.cfg, self.max_batch, self.max_len, ctx.compute_dtype,
                ctx, per_slot=True)

    @staticmethod
    def serve_insert(pool: lm.DecodeState, new: lm.DecodeState,
                     slots: jax.Array) -> lm.DecodeState:
        """Scatter a freshly prefilled ``new`` state (rows = admit chunk)
        into the pool at ``slots``; out-of-bounds slot ids (pad lanes) are
        dropped.  Compiled as ``jit_serve_insert``."""
        def upd(p, n):
            return p.at[:, slots].set(n.astype(p.dtype), mode="drop")
        kv = None if pool.kv is None else jax.tree.map(upd, pool.kv, new.kv)
        ssm = None if pool.ssm is None else jax.tree.map(upd, pool.ssm,
                                                         new.ssm)
        length = pool.length.at[slots].set(
            jnp.broadcast_to(new.length, slots.shape).astype(jnp.int32),
            mode="drop")
        return lm.DecodeState(kv, ssm, length)

    def _get_insert(self, new_state):
        """AOT slot-insert scatter; its shapes depend only on the pool and
        the admit chunk (the KV capacity is fixed by max_len, not by the
        prompt bucket), so ONE executable covers every admission.  The pool
        state cycles insert -> decode -> insert, so the pool is committed to
        the scatter's natural output sharding and both executables are
        pinned to it (a sharding drift would reject the second call)."""
        if self._insert_exec is None:
            t0 = time.perf_counter()
            # seed the (freshly built, single-device) pool with the prefill
            # output's shardings — same specs, pool-sized batch axis — so the
            # two states live on the same devices; the specs are rank-safe
            # (length: new is scalar/replicated, pool (B,) stays replicated)
            self._state = jax.device_put(
                self._state, jax.tree.map(lambda x: x.sharding, new_state))
            pool_avals = _avals_like(self._state)
            new_avals = _avals_like(new_state)
            slots = jax.ShapeDtypeStruct((self.admit_chunk,), jnp.int32)
            exe = (jax.jit(self.serve_insert)
                   .lower(pool_avals, new_avals, slots).compile())
            self.compile_count += 1
            out_sh = exe.output_shardings
            in_sh = jax.tree.map(lambda x: x.sharding, pool_avals)
            if not _same_shardings(out_sh, in_sh):
                self._state = jax.device_put(self._state, out_sh)
                exe = (jax.jit(self.serve_insert, out_shardings=out_sh)
                       .lower(_avals_like(self._state), new_avals, slots)
                       .compile())
                self.compile_count += 1
            self._insert_exec = exe
            self.compile_s += time.perf_counter() - t0
        return self._insert_exec

    def warmup(self, params) -> float:
        """AOT-compile every (admit-chunk × bucket) prefill executable, the
        pool decode step and the slot-insert scatter, and run each once;
        returns seconds spent.  After warmup, ``compile_count`` must stay
        flat under any admission pattern whose prompts fit the buckets
        (compilation-counter test)."""
        t0 = time.perf_counter()
        self._ensure_pool()
        for s in self.buckets:
            new = self._dry_prefill(params, self.admit_chunk, s)
        # every slot id out of range: the scatter drops all rows and the
        # pool comes back unchanged
        none = jnp.asarray(np.full((self.admit_chunk,), self.max_batch,
                                   np.int32))
        self._state = self._get_insert(new)(self._state, new, none)
        dec = self.get_decode(params, self._state, self.max_batch)
        jax.block_until_ready(dec(params, self._state,
                                  jnp.asarray(self._tok)))
        return time.perf_counter() - t0

    # --------------------------------------------------------- scheduling ---

    def pending(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slots)

    def free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    def _retire_or_keep(self, i: int, tok: int, retired: list):
        """Append ``tok`` to slot i's request; retire the slot on eos or
        max_new (feeding the emit path), else keep the token for the next
        decode step."""
        r = self.slots[i]
        r.output.append(tok)
        if (len(r.output) >= r.max_new or
                (self.eos_id is not None and tok == self.eos_id)):
            r.done = True
            self.slots[i] = None
            self._tok[i] = self.pad_id
            self.finished.append(r)
            if self.emit is not None:
                self.emit(r)
            retired.append(r)
        else:
            self._tok[i] = tok

    def _fetch(self, x) -> np.ndarray:
        """Block until a device result is on the host: the ``engine.wait``
        span, its time added to ``wait_s``."""
        t0 = time.perf_counter()
        with TraceAnnotation("engine.wait"):
            out = np.asarray(x)
        self.wait_s += time.perf_counter() - t0
        return out

    def _admit(self, params, retired: list) -> list[Request]:
        """Prefill-insert queued requests into free slots, one admit chunk
        at a time, while the rest of the pool's state sits untouched."""
        admitted = []
        while self.queue and self.free_slots():
            free = self.free_slots()
            take = min(self.admit_chunk, len(self.queue), len(free))
            reqs = [self.queue.popleft() for _ in range(take)]
            now = time.perf_counter()
            for r in reqs:
                r.admitted_at = now
            s = max(self.bucket_of(len(r.prompt)) for r in reqs)
            exe = self.get_prefill(params, self.admit_chunk, s)  # pre-timed
            self._ensure_pool()
            t0 = time.perf_counter()
            with TraceAnnotation("engine.prefill", rids=[r.rid for r in reqs],
                                 bucket=s, rows=len(reqs)):
                toks = np.full((self.admit_chunk, s), self.pad_id, np.int32)
                valid = np.zeros((self.admit_chunk, s), bool)
                for j, r in enumerate(reqs):
                    toks[j, s - len(r.prompt):] = r.prompt      # left-pad
                    valid[j, s - len(r.prompt):] = True
                t_batch = jnp.asarray(toks)
                if self.traffic is not None:
                    first, new_state, traffic = exe(
                        params, t_batch, self.traffic, jnp.asarray(valid))
                    self.traffic = _uncommitted(traffic)
                    self._record_load()
                else:
                    first, new_state = exe(params, t_batch)
                first = self._fetch(first)
            end = time.perf_counter()
            self.prefill_s += end - t0
            self.prefill_calls += 1
            # pad lanes point at slot id max_batch -> dropped by the scatter
            slot_arr = np.full((self.admit_chunk,), self.max_batch, np.int32)
            for j, r in enumerate(reqs):
                i = free[j]
                slot_arr[j] = i
                self.slots[i] = r
                r.ttft_s = end - r.submitted_at
            with TraceAnnotation("engine.insert"):
                self._state = self._get_insert(new_state)(
                    self._state, new_state, jnp.asarray(slot_arr))
            for j, r in enumerate(reqs):
                # the prefill's argmax IS the request's first token (TTFT
                # token); a max_new=1 request retires without ever decoding
                self._retire_or_keep(int(slot_arr[j]), int(first[j]), retired)
            admitted.extend(reqs)
        return admitted

    def step(self, params) -> list[Request]:
        """Admit into free slots, then decode the whole pool one token.
        Returns the requests retired this step."""
        t_step = time.perf_counter()
        with TraceAnnotation("engine.step"):
            retired = self._step(params)
        self.step_s += time.perf_counter() - t_step
        self.steps += 1
        return retired

    def _step(self, params) -> list[Request]:
        retired: list[Request] = []
        with TraceAnnotation("engine.admit"):
            self._admit(params, retired)
        occupied = [i for i, r in enumerate(self.slots) if r is not None]
        self.occupancy.append(len(occupied) / self.max_batch)
        if not occupied:
            return retired
        self._ensure_pool()
        dec = self.get_decode(params, self._state, self.max_batch)
        t0 = time.perf_counter()
        with TraceAnnotation("engine.decode", rows=len(occupied)):
            tok, self._state = dec(params, self._state,
                                   jnp.asarray(self._tok))
            tok = self._fetch(tok)
        self.decode_s += time.perf_counter() - t0
        self.decode_steps += 1
        self.decode_tokens += len(occupied)
        with TraceAnnotation("engine.retire"):
            for i in occupied:
                self._retire_or_keep(i, int(tok[i]), retired)
        return retired

    def run(self, params) -> list[Request]:
        """Step until the queue and every slot drain; returns all finished."""
        out: list[Request] = []
        while self.pending():
            out.extend(self.step(params))
        return out

    def stats(self) -> dict:
        out = super().stats()
        if self.occupancy:
            out["mean_slot_occupancy"] = float(np.mean(self.occupancy))
            out["decode_steps"] = self.decode_steps
        if self.decode_s > 0:
            out["decode_tok_s"] = self.decode_tokens / self.decode_s
        if self.steps:
            out.update(steps=self.steps, step_s=self.step_s,
                       wait_s=self.wait_s, prefill_calls=self.prefill_calls,
                       prefill_s=self.prefill_s)
        # a TTFT is the queue wait, then the admit chunk's prefill
        waits = [r.admitted_at - r.submitted_at for r in self.finished
                 if r.admitted_at is not None]
        if waits:
            out["p50_queue_wait_s"] = float(np.percentile(waits, 50))
            out["p95_queue_wait_s"] = float(np.percentile(waits, 95))
        if self.prefill_calls:
            out["mean_prefill_s"] = self.prefill_s / self.prefill_calls
        return out
