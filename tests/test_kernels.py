"""Per-kernel Pallas (interpret mode) vs pure-jnp oracle, shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.segment_gather import segment_gather
from repro.kernels.segment_scatter_add import segment_scatter_add


@pytest.mark.parametrize("t,r,d,br,dtype", [
    (37, 16, 256, 8, jnp.float32),
    (64, 64, 512, 32, jnp.bfloat16),
    (8, 128, 128, 16, jnp.float32),
    (5, 3, 256, 32, jnp.bfloat16),
])
def test_segment_gather_sweep(t, r, d, br, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    src = jax.random.normal(ks[0], (t, d)).astype(dtype)
    idx = jax.random.randint(ks[1], (r,), -1, t).astype(jnp.int32)
    out = segment_gather(src, idx, block_r=br, interpret=True)
    expect = ref.segment_gather_ref(src, idx)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=0)


@pytest.mark.parametrize("r,out_rows,d,br,dtype", [
    (8, 5, 256, 8, jnp.float32),
    (32, 8, 512, 32, jnp.float32),
    (16, 4, 128, 32, jnp.bfloat16),
    (45, 6, 256, 8, jnp.float32),         # ragged tail, many revisits
])
def test_segment_scatter_add_sweep(r, out_rows, d, br, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    src = jax.random.normal(ks[0], (r, d)).astype(dtype)
    dst = jax.random.randint(ks[1], (r,), -1, out_rows).astype(jnp.int32)
    gates = jax.random.uniform(ks[2], (r,))
    out = segment_scatter_add(src, dst, gates, out_rows, block_r=br,
                              interpret=True)
    expect = ref.segment_scatter_add_ref(src, dst, gates, out_rows)
    tol = 1e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol)


@pytest.mark.parametrize("g,c,d,f,dtype", [
    (4, 256, 128, 256, jnp.bfloat16),
    (2, 128, 256, 128, jnp.float32),
    (8, 128, 128, 128, jnp.bfloat16),
])
def test_grouped_matmul_sweep(g, c, d, f, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = (jax.random.normal(ks[0], (g, c, d)) * 0.3).astype(dtype)
    w = (jax.random.normal(ks[1], (g, d, f)) * 0.1).astype(dtype)
    counts = jax.random.randint(ks[2], (g,), 0, c + 1).astype(jnp.int32)
    out = grouped_matmul(x, w, counts, interpret=True)
    expect = ref.grouped_matmul_ref(x, w, counts)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


def test_gather_scatter_roundtrip_is_identity_when_bijective():
    d = 128
    src = jax.random.normal(jax.random.PRNGKey(3), (16, d))
    perm = jax.random.permutation(jax.random.PRNGKey(4), 16).astype(jnp.int32)
    gathered = segment_gather(src, perm, interpret=True)
    inv = jnp.zeros(16, jnp.int32).at[perm].set(jnp.arange(16, dtype=jnp.int32))
    back = segment_gather(gathered, inv, interpret=True)
    np.testing.assert_allclose(np.asarray(back), np.asarray(src))


@pytest.mark.parametrize("g,c,d,f", [(3, 64, 32, 64), (2, 96, 64, 32)])
def test_grouped_matmul_partial_block_rows_zeroed(g, c, d, f):
    """Rows at or past counts[g] must be EXACTLY zero even when the partial
    block's padding rows hold garbage — downstream scatter-adds land them."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (g, c, d)) * 0.3
    w = jax.random.normal(ks[1], (g, d, f)) * 0.1
    counts = jax.random.randint(ks[2], (g,), 0, c + 1).astype(jnp.int32)
    # poison every dead row: pre-fix, any row inside an occupied block but
    # past counts[g] leaked garbage into the output
    live = counts[:, None] > jnp.arange(c)[None, :]
    x = jnp.where(live[..., None], x, 1e6)
    for block_c in (32, c):
        out = grouped_matmul(x, w, counts, block_c=block_c, interpret=True)
        assert np.all(np.asarray(out)[~np.asarray(live)] == 0.0), block_c
        expect = ref.grouped_matmul_ref(x, w, counts)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("s,e,c,d,f,dtype", [
    (2, 3, 64, 32, 64, jnp.float32),
    (1, 2, 128, 64, 128, jnp.bfloat16),
    (4, 1, 96, 32, 32, jnp.float32),      # non-power-of-two capacity
])
def test_fused_swiglu_matches_ref(s, e, c, d, f, dtype):
    from repro.kernels.fused_staging import fused_swiglu_pallas
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    x = (jax.random.normal(ks[0], (s, e, c, d)) * 0.3).astype(dtype)
    w1 = (jax.random.normal(ks[1], (e, d, f)) * 0.1).astype(dtype)
    w3 = (jax.random.normal(ks[2], (e, d, f)) * 0.1).astype(dtype)
    w2 = (jax.random.normal(ks[3], (e, f, d)) * 0.1).astype(dtype)
    counts = jax.random.randint(ks[4], (s, e), 0, c + 1).astype(jnp.int32)
    out = fused_swiglu_pallas(x, w1, w3, w2, counts, block_c=32, block_f=32,
                              interpret=True)
    expect = ref.fused_swiglu_ref(x, w1, w3, w2, counts)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)
    # dead rows exactly zero regardless of dtype
    dead = ~(np.asarray(counts)[..., None] > np.arange(c))
    assert np.all(np.asarray(out, np.float32)[dead] == 0.0)


def test_fused_swiglu_grads_match_oracle(monkeypatch):
    """jax.grad through ops.fused_swiglu (pallas fwd + custom VJP) must match
    the plain-jnp differentiable oracle for every operand."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    from repro.kernels import ops
    s, e, c, d, f = 2, 2, 32, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (s, e, c, d)) * 0.3
    w1 = jax.random.normal(ks[1], (e, d, f)) * 0.1
    w3 = jax.random.normal(ks[2], (e, d, f)) * 0.1
    w2 = jax.random.normal(ks[3], (e, f, d)) * 0.1
    counts = jax.random.randint(ks[4], (s, e), 0, c + 1).astype(jnp.int32)

    def oracle(x, w1, w3, w2):
        h = jnp.einsum("secd,edf->secf", x, w1)
        u = jnp.einsum("secd,edf->secf", x, w3)
        o = jnp.einsum("secf,efd->secd", jax.nn.silu(h) * u, w2)
        livem = counts[..., None] > jnp.arange(c)
        return jnp.sum(jnp.where(livem[..., None], o, 0) ** 2)

    def kernel(x, w1, w3, w2):
        return jnp.sum(ops.fused_swiglu(x, w1, w3, w2, counts) ** 2)

    gk = jax.grad(kernel, argnums=(0, 1, 2, 3))(x, w1, w3, w2)
    go = jax.grad(oracle, argnums=(0, 1, 2, 3))(x, w1, w3, w2)
    for a, b, name in zip(gk, go, "x w1 w3 w2".split()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5, err_msg=name)


def test_staging_vjps_match_jnp_transpose(monkeypatch):
    """gather/scatter-add custom VJPs vs autodiff through the jnp refs."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    from repro.kernels import ops
    t, r, d = 12, 20, 64
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    src = jax.random.normal(ks[0], (t, d))
    idx = jax.random.randint(ks[1], (r,), -1, t).astype(jnp.int32)
    gates = jax.random.uniform(ks[2], (r,)) + 0.1

    g1 = jax.grad(lambda s: jnp.sum(ops.segment_gather(s, idx) ** 2))(src)
    g2 = jax.grad(lambda s: jnp.sum(ref.segment_gather_ref(s, idx) ** 2))(src)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)

    rows = jax.random.normal(ks[0], (r, d))
    k_fn = lambda s, g: jnp.sum(ops.segment_scatter_add(s, idx, g, t) ** 2)
    r_fn = lambda s, g: jnp.sum(ref.segment_scatter_add_ref(s, idx, g, t) ** 2)
    gk = jax.grad(k_fn, argnums=(0, 1))(rows, gates)
    gr = jax.grad(r_fn, argnums=(0, 1))(rows, gates)
    for a, b, name in zip(gk, gr, ("src", "gates")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, err_msg=name)


def test_backend_resolution_is_per_call(monkeypatch):
    """Toggling REPRO_USE_PALLAS between calls must flip the dispatch path
    in BOTH orders — a cached backend()/use_pallas() answer went stale."""
    from repro.kernels import ops
    src = jnp.arange(12.0).reshape(4, 3)
    idx = jnp.array([2, 0, -1], jnp.int32)
    taken = []
    real_pallas, real_ref = ops._gather_pallas, ops.ref.segment_gather_ref
    monkeypatch.setattr(ops, "_gather_pallas",
                        lambda *a, **k: taken.append("pallas")
                        or real_pallas(*a, **k))
    monkeypatch.setattr(ops.ref, "segment_gather_ref",
                        lambda *a, **k: taken.append("ref")
                        or real_ref(*a, **k))
    for order in (("1", "0", "1"), ("0", "1", "0")):
        taken.clear()
        for env in order:
            monkeypatch.setenv("REPRO_USE_PALLAS", env)
            out = ops.segment_gather(src, idx)
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(real_ref(src, idx)))
        expect = ["pallas" if e == "1" else "ref" for e in order]
        assert taken == expect, (order, taken)
