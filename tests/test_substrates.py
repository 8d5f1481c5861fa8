"""Optimizer / checkpoint / data / compression / runtime substrate tests."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic fallback so the suite still runs
    from _hypothesis_compat import given, settings, st

from repro.checkpoint import checkpointer
from repro.data.pipeline import SyntheticLM, ZipfNgramLM
from repro.optim import adamw
from repro.parallel import compress


# ------------------------------------------------------------- optimizer ---

def test_adamw_minimises_quadratic():
    target = jnp.array([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3, jnp.bfloat16)}
    opt = adamw.init(params)
    cfg = adamw.AdamWConfig(lr=0.1, warmup_steps=0, total_steps=200,
                            weight_decay=0.0, clip_norm=100.0)

    @jax.jit
    def step(p, o):
        loss, g = jax.value_and_grad(
            lambda p: jnp.sum((p["w"].astype(jnp.float32) - target) ** 2))(p)
        p, o, m = adamw.update(g, o, p, cfg)
        return p, o, loss

    loss0 = None
    for _ in range(150):
        params, opt, loss = step(params, opt)
        loss0 = loss0 if loss0 is not None else float(loss)
    assert float(loss) < 0.05 * loss0


def test_clip_bounds_update():
    params = {"w": jnp.zeros(4, jnp.float32)}
    opt = adamw.init(params)
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=0, clip_norm=1e-3,
                            weight_decay=0.0)
    g = {"w": jnp.full(4, 1e6)}
    p2, _, m = adamw.update(g, opt, params, cfg)
    assert float(m["grad_norm"]) > 1e5
    assert float(jnp.max(jnp.abs(p2["w"]))) < 2.0  # clipped step is bounded


def test_zero1_specs_add_data_axis():
    from jax.sharding import PartitionSpec as P
    specs = {"w": P(None, "model")}
    shapes = {"w": jax.ShapeDtypeStruct((64, 32), jnp.float32)}
    out = adamw.zero1_specs(specs, shapes, data_size=16)
    assert out["w"] == P("data", "model")


# ------------------------------------------------------------ checkpoint ---

def test_checkpoint_roundtrip_bf16(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
            "b": {"c": jnp.float32(3.5), "s": jnp.int32(7)}}
    h = checkpointer.save(str(tmp_path), tree, step=3, async_=True)
    checkpointer.wait(h)
    assert checkpointer.latest_step(str(tmp_path)) == 3
    like = jax.tree.map(jnp.zeros_like, tree)
    out, step = checkpointer.restore(str(tmp_path), like)
    assert step == 3
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))


def test_checkpoint_latest_is_atomic(tmp_path):
    tree = {"a": jnp.ones(3)}
    checkpointer.save(str(tmp_path), tree, step=1, async_=False)
    checkpointer.save(str(tmp_path), {"a": jnp.ones(3) * 2}, step=2,
                      async_=False)
    out, step = checkpointer.restore(str(tmp_path), tree)
    assert step == 2 and float(out["a"][0]) == 2.0
    # older step still restorable explicitly
    out1, _ = checkpointer.restore(str(tmp_path), tree, step=1)
    assert float(out1["a"][0]) == 1.0


# ------------------------------------------------------------------ data ---

def test_loader_determinism():
    a = ZipfNgramLM(1000, 16, 4, seed=7).batch_at(5)
    b = ZipfNgramLM(1000, 16, 4, seed=7).batch_at(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = ZipfNgramLM(1000, 16, 4, seed=8).batch_at(5)
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert a["labels"].max() < 1000 and a["labels"].min() >= 0


def test_labels_shifted():
    b = SyntheticLM(50, 8, 2, seed=0).batch_at(0)
    assert b["tokens"].shape == b["labels"].shape == (2, 8)


# ----------------------------------------------------------- compression ---

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 1000))
def test_quantize_error_bound(seed):
    r = np.random.default_rng(seed)
    x = jnp.array(r.normal(0, 3, (300,)), jnp.float32)
    q, s = compress.quantize(x, block=64)
    deq = compress.dequantize(q, s, x.shape, block=64)
    # per-block max error <= scale/2 = max|block|/254
    err = np.abs(np.asarray(deq - x))
    bound = np.abs(np.asarray(x)).max() / 127.0
    assert err.max() <= bound + 1e-6


def test_error_feedback_reduces_bias():
    """With EF, the running sum of dequantised grads tracks the true sum."""
    r = np.random.default_rng(0)
    g = {"w": jnp.array(r.normal(0, 1, (128,)), jnp.float32)}
    ef = compress.init_error(g)
    total_true = np.zeros(128)
    total_deq = np.zeros(128)
    for i in range(20):
        gi = {"w": jnp.array(r.normal(0, 1, (128,)), jnp.float32)}
        qs, treedef, ef = compress.compress_grads(gi, ef, block=64)
        deq = compress.decompress_grads(qs, treedef, jax.tree.leaves(gi))
        total_true += np.asarray(gi["w"])
        total_deq += np.asarray(jax.tree.leaves(deq)[0])
    resid = np.abs(total_true - total_deq).max()
    scale = np.abs(total_true).max()
    assert resid < 0.15 * scale  # EF keeps the accumulated signal unbiased


# --------------------------------------------------------------- runtime ---

def test_fault_tolerant_restart(tmp_path):
    from repro.runtime.fault_tolerance import RunConfig, run_training

    calls = {"n": 0}

    def step_fn(params, opt, batch):
        calls["n"] += 1
        return params + 1, opt, {"loss": jnp.float32(1.0)}

    cfg = RunConfig(total_steps=10, ckpt_dir=str(tmp_path), ckpt_every=2,
                    inject_failure_at=5)
    (params, opt), run = run_training(
        step_fn, (jnp.int32(0), jnp.int32(0)), lambda s: None, cfg,
        log=lambda *a: None)
    assert run.restarts == 1
    assert int(params) == 10   # restarted from step 4, replayed to 10


def test_no_restarts_reraises_and_no_checkpoints(tmp_path):
    """max_restarts=0: the first step failure ends the run (it is never
    retried into a run that exits 0); ckpt_every=0 writes nothing."""
    from repro.runtime.fault_tolerance import (InjectedFailure, RunConfig,
                                               run_training)

    def step_fn(params, opt, batch):
        return params + 1, opt, {"loss": jnp.float32(1.0)}

    init = (jnp.int32(0), jnp.int32(0))
    with pytest.raises(InjectedFailure):
        run_training(step_fn, init, lambda s: None,
                     RunConfig(total_steps=4, ckpt_dir=str(tmp_path),
                               inject_failure_at=1, max_restarts=0),
                     log=lambda *a: None)
    (params, _), run = run_training(
        step_fn, init, lambda s: None,
        RunConfig(total_steps=4, ckpt_dir=str(tmp_path / "none"),
                  ckpt_every=0), log=lambda *a: None)
    assert int(params) == 4 and run.restarts == 0
    assert not (tmp_path / "none").exists()


def test_elastic_relayout():
    from repro.core.routing import ExpertPlacement
    from repro.runtime.elastic import relayout_expert_weights
    old = ExpertPlacement(n_experts=8, ep=4, node_size=2)   # 2 experts/lane
    new = ExpertPlacement(n_experts=8, ep=8, node_size=2)   # 1 expert/lane
    w = np.arange(4 * 2 * 3, dtype=np.float32).reshape(4, 2, 3)
    out = relayout_expert_weights(w, old, new)
    assert out.shape == (8, 1, 3)
    np.testing.assert_array_equal(out[5, 0], w[2, 1])  # expert 5 = lane2 slot1
