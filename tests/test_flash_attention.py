"""Flash attention (fwd + custom VJP) vs naive oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic fallback so the suite still runs
    from _hypothesis_compat import given, settings, st

from repro.layers.attention import (KVCache, cache_update, decode_attention,
                                    flash_attention, init_kv_cache)


def naive(q, k, v, qp, kp, causal, window):
    b, sq, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qr = q.reshape(b, sq, hkv, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k).astype(jnp.float32) * hd ** -0.5
    m = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        m &= qp[:, None] >= kp[None, :]
    if window:
        m &= qp[:, None] - kp[None, :] < window
    s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, -1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return o.reshape(b, sq, hq, hd)


@pytest.mark.parametrize("causal,window,qb,kb,hq,hkv", [
    (True, None, 16, 16, 4, 2),
    (False, None, 32, 16, 4, 4),
    (True, 8, 16, 32, 8, 2),
    (True, None, 64, 64, 2, 1),
])
def test_flash_matches_naive_with_grads(causal, window, qb, kb, hq, hkv):
    B, S, hd = 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, hq, hd))
    k = jax.random.normal(ks[1], (B, S, hkv, hd))
    v = jax.random.normal(ks[2], (B, S, hkv, hd))
    qp = kp = jnp.arange(S)
    out = flash_attention(q, k, v, qp, kp, causal, window, qb, kb)
    ref = naive(q, k, v, qp, kp, causal, window)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    f = lambda q, k, v: flash_attention(q, k, v, qp, kp, causal, window, qb, kb).sum()
    n = lambda q, k, v: naive(q, k, v, qp, kp, causal, window).sum()
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(n, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gn, "q k v".split()):
        assert float(jnp.max(jnp.abs(a - b_))) < 5e-5, name


@settings(max_examples=10, deadline=None)
@given(st.sampled_from([16, 32, 64]), st.sampled_from([8, 16, 32]),
       st.booleans())
def test_flash_block_size_invariance(qb, kb, causal):
    B, S, hq, hkv, hd = 1, 64, 4, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, hq, hd))
    k = jax.random.normal(ks[1], (B, S, hkv, hd))
    v = jax.random.normal(ks[2], (B, S, hkv, hd))
    qp = kp = jnp.arange(S)
    a = flash_attention(q, k, v, qp, kp, causal, None, qb, kb)
    b = flash_attention(q, k, v, qp, kp, causal, None, 64, 64)
    assert float(jnp.max(jnp.abs(a - b))) < 2e-5


def test_decode_matches_prefill_tail():
    """Decoding token t against a cache == full attention row t."""
    B, S, hq, hkv, hd = 2, 24, 4, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, S, hq, hd))
    k = jax.random.normal(ks[1], (B, S, hkv, hd))
    v = jax.random.normal(ks[2], (B, S, hkv, hd))
    qp = kp = jnp.arange(S)
    full = naive(q, k, v, qp, kp, True, None)
    cache = init_kv_cache(B, S, hkv, hd, jnp.float32)
    for t in range(S):
        cache = cache_update(cache, k[:, t:t+1], v[:, t:t+1])
        out = decode_attention(q[:, t:t+1], cache)
        assert float(jnp.max(jnp.abs(out[:, 0] - full[:, t]))) < 2e-5, t


def test_ring_cache_window():
    """Ring cache of size W must equal sliding-window attention."""
    B, S, W, hq, hkv, hd = 1, 32, 8, 2, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, hq, hd))
    k = jax.random.normal(ks[1], (B, S, hkv, hd))
    v = jax.random.normal(ks[2], (B, S, hkv, hd))
    qp = kp = jnp.arange(S)
    ref = naive(q, k, v, qp, kp, True, W)
    cache = init_kv_cache(B, S, hkv, hd, jnp.float32, window=W)
    assert cache.k.shape[1] == W
    for t in range(S):
        cache = cache_update(cache, k[:, t:t+1], v[:, t:t+1])
        out = decode_attention(q[:, t:t+1], cache)
        assert float(jnp.max(jnp.abs(out[:, 0] - ref[:, t]))) < 2e-5, t


@pytest.mark.parametrize("offset,window", [(32, None), (32, 24), (7, None)])
def test_flash_shifted_positions_match_naive(offset, window):
    """Island chunks carry SHIFTED q positions (this lane's stripe, RoPE'd at
    absolute offsets) against the full gathered k/v.  Index-based block
    pruning silently zeroed real scores here — position-bound pruning must
    agree with the naive oracle, forward and grads."""
    B, Sq, Sk, hq, hkv, hd = 2, 32, 64, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, Sq, hq, hd))
    k = jax.random.normal(ks[1], (B, Sk, hkv, hd))
    v = jax.random.normal(ks[2], (B, Sk, hkv, hd))
    qp = jnp.arange(Sq) + offset
    kp = jnp.arange(Sk)
    out = flash_attention(q, k, v, qp, kp, True, window, 16, 16)
    expect = naive(q, k, v, qp, kp, True, window)
    assert float(jnp.max(jnp.abs(out - expect))) < 2e-5

    f = lambda q, k, v: flash_attention(q, k, v, qp, kp, True, window,
                                        16, 16).sum()
    n = lambda q, k, v: naive(q, k, v, qp, kp, True, window).sum()
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(n, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gn, "q k v".split()):
        assert float(jnp.max(jnp.abs(a - b_))) < 5e-5, name


def test_flash_shifted_positions_under_jit():
    """Traced positions can't be pruned statically; the runtime-gated path
    must still match the oracle (and not crash on concretization)."""
    B, Sq, Sk, hq, hkv, hd = 1, 32, 64, 2, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (B, Sq, hq, hd))
    k = jax.random.normal(ks[1], (B, Sk, hkv, hd))
    v = jax.random.normal(ks[2], (B, Sk, hkv, hd))
    kp = jnp.arange(Sk)

    @jax.jit
    def f(q, k, v, qp):
        return flash_attention(q, k, v, qp, kp, True, None, 16, 16)

    for off in (0, 32):
        qp = jnp.arange(Sq) + off
        out = f(q, k, v, qp)
        expect = naive(q, k, v, qp, kp, True, None)
        assert float(jnp.max(jnp.abs(out - expect))) < 2e-5, off


@pytest.mark.parametrize("offset,window,hq,hkv", [
    (0, None, 4, 2), (32, None, 4, 2), (32, 24, 8, 2), (7, None, 2, 1),
])
def test_pallas_flash_matches_naive(offset, window, hq, hkv):
    """The Pallas kernel (interpret mode) under the same shifted layouts,
    forward and custom-VJP grads."""
    from repro.kernels.flash_attention import flash_attention as pallas_flash
    B, Sq, Sk, hd = 2, 32, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, Sq, hq, hd))
    k = jax.random.normal(ks[1], (B, Sk, hkv, hd))
    v = jax.random.normal(ks[2], (B, Sk, hkv, hd))
    qp = jnp.arange(Sq) + offset
    kp = jnp.arange(Sk)
    out = pallas_flash(q, k, v, qp, kp, True, window, 16, 16, True)
    expect = naive(q, k, v, qp, kp, True, window)
    assert float(jnp.max(jnp.abs(out - expect))) < 2e-5

    f = lambda q, k, v: pallas_flash(q, k, v, qp, kp, True, window,
                                     16, 16, True).sum()
    n = lambda q, k, v: naive(q, k, v, qp, kp, True, window).sum()
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(n, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gn, "q k v".split()):
        assert float(jnp.max(jnp.abs(a - b_))) < 5e-5, name


def test_ops_flash_dispatcher_routes_by_env(monkeypatch):
    from repro.kernels import ops
    B, S, hq, hkv, hd = 1, 32, 2, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (B, S, hq, hd))
    k = jax.random.normal(ks[1], (B, S, hkv, hd))
    v = jax.random.normal(ks[2], (B, S, hkv, hd))
    pos = jnp.arange(S)
    outs = {}
    for env in ("0", "1"):
        monkeypatch.setenv("REPRO_USE_PALLAS", env)
        outs[env] = ops.flash_attention(q, k, v, pos, pos, causal=True,
                                        q_block=16, kv_block=16)
    expect = naive(q, k, v, pos, pos, True, None)
    for env, out in outs.items():
        assert float(jnp.max(jnp.abs(out - expect))) < 2e-5, env
