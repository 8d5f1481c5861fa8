"""Pallas TPU kernel: position-safe block-skipping flash attention.

The island hot path: ``fusco.tx_attention`` calls attention with a SHIFTED
q-position chunk (this lane's sequence stripe, RoPE'd at absolute positions)
against the full all-gathered k/v.  Block visibility therefore cannot be
derived from block indices — this kernel scalar-prefetches per-block position
*bounds* (min/max of the actual ``q_positions``/``k_positions``) and skips a
(q-block, kv-block) pair only when the bounds prove every entry masked:

    causal:  visible iff  min(k_pos[j]) <= max(q_pos[i])
    window:  visible iff  min(q_pos[i]) - max(k_pos[j]) < window

the same contract as the lax ``layers.attention.flash_attention`` after its
position-safety fix — both now agree with ``reference_attention`` for any
position layout, and both earn sub-quadratic cost by skipping.

Forward only: online softmax per q-block in VMEM scratch over the sequential
kv grid axis, emitting the output AND the per-row lse.  The backward is the
lax flash VJP (same O(S) residual recompute), wired via custom_vjp in
:func:`flash_attention`.

Grid: (B, Hkv, G, nq, nk) — GQA head groups are grid axes, kv blocks
innermost so the scratch accumulator carries one q-block's running softmax.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(qmn_ref, qmx_ref, kmn_ref, kmx_ref,
                  qp_ref, kp_ref, q_ref, k_ref, v_ref,
                  o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                  causal, window, scale):
    qi = pl.program_id(3)
    ki = pl.program_id(4)
    nk = pl.num_programs(4)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # position-bound visibility: skip only when provably fully masked
    vis = jnp.bool_(True)
    if causal:
        vis &= kmn_ref[ki] <= qmx_ref[qi]
    if window is not None:
        vis &= qmn_ref[qi] - kmx_ref[ki] < window

    @pl.when(vis)
    def _block():
        q = q_ref[0, 0, 0]                               # (qb, hd)
        k = k_ref[0, 0]                                  # (kb, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (qb, kb)
        qpos = qp_ref[0]                                 # (qb, 1) int32
        kpos = kp_ref[0]                                 # (1, kb)
        mask = jnp.ones_like(s, jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                              # (qb, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v_ref[0, 0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _out():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0, 0] = m_ref[...] + jnp.log(l)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_block",
                                             "kv_block", "interpret"))
def _flash_fwd_pallas(q, k, v, q_positions, k_positions, causal, window,
                      q_block, kv_block, interpret):
    """Returns (out (B,Sq,Hq,hd), lse (B,nq,Hkv,G,qb)) — lse in the layout
    the lax flash backward consumes."""
    b, sq, hq, hd = q.shape
    _, sk, hkv, _ = k.shape
    g = hq // hkv
    scale = hd ** -0.5
    qb, kb = min(q_block, sq), min(kv_block, sk)
    nq, nk = sq // qb, sk // kb
    assert sq % qb == 0 and sk % kb == 0, (sq, qb, sk, kb)

    # (B, Hkv, G, Sq, hd) — head-major split matches the lax flash reshape
    qr = q.transpose(0, 2, 1, 3).reshape(b, hkv, g, sq, hd)
    kr = k.transpose(0, 2, 1, 3)                         # (B, Hkv, Sk, hd)
    vr = v.transpose(0, 2, 1, 3)
    # positions as a column per q-block and a row per kv-block, so each
    # block's last two dims are full or (8, 128)-aligned on the TPU
    qp = q_positions.astype(jnp.int32).reshape(nq, qb, 1)
    kp = k_positions.astype(jnp.int32).reshape(nk, 1, kb)
    qmn, qmx = qp.min(axis=(1, 2)), qp.max(axis=(1, 2))
    kmn, kmx = kp.min(axis=(1, 2)), kp.max(axis=(1, 2))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,                # qmin, qmax, kmin, kmax bounds
        grid=(b, hkv, g, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qb, 1),
                         lambda bi, hi, gi, qi, ki, *s: (qi, 0, 0)),
            pl.BlockSpec((1, 1, kb),
                         lambda bi, hi, gi, qi, ki, *s: (ki, 0, 0)),
            pl.BlockSpec((1, 1, 1, qb, hd),
                         lambda bi, hi, gi, qi, ki, *s: (bi, hi, gi, qi, 0)),
            pl.BlockSpec((1, 1, kb, hd),
                         lambda bi, hi, gi, qi, ki, *s: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, kb, hd),
                         lambda bi, hi, gi, qi, ki, *s: (bi, hi, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, qb, hd),
                         lambda bi, hi, gi, qi, ki, *s: (bi, hi, gi, qi, 0)),
            pl.BlockSpec((1, 1, 1, qb, 1),
                         lambda bi, hi, gi, qi, ki, *s: (bi, hi, gi, qi, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((qb, hd), jnp.float32),
                        pltpu.VMEM((qb, 1), jnp.float32),
                        pltpu.VMEM((qb, 1), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, window=window,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hkv, g, sq, hd), q.dtype),
                   jax.ShapeDtypeStruct((b, hkv, g, sq, 1), jnp.float32)],
        interpret=interpret,
        name="_flash_fwd_pallas",
    )
    o, lse = fn(qmn, qmx, kmn, kmx, qp, kp, qr, kr, vr)
    out = o.reshape(b, hq, sq, hd).transpose(0, 2, 1, 3)
    lse = jnp.moveaxis(lse.reshape(b, hkv, g, nq, qb), 3, 1)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def flash_attention(q, k, v, q_positions, k_positions, causal=True,
                    window=None, q_block=512, kv_block=512, interpret=False):
    """Pallas flash attention, position-safe (shifted island chunks / offset
    layouts mask and block-skip correctly).  Same signature/semantics as
    ``layers.attention.flash_attention`` plus ``interpret`` (CPU validation
    mode).  Backward: the lax flash VJP on the pallas forward's residuals."""
    out, _ = _flash_fwd_pallas(q, k, v, q_positions, k_positions, causal,
                               window, q_block, kv_block, interpret)
    return out


def _flash_vjp_fwd(q, k, v, q_positions, k_positions, causal, window,
                   q_block, kv_block, interpret):
    out, lse = _flash_fwd_pallas(q, k, v, q_positions, k_positions, causal,
                                 window, q_block, kv_block, interpret)
    return out, (q, k, v, q_positions, k_positions, out, lse)


def _flash_vjp_bwd(causal, window, q_block, kv_block, interpret, res, dout):
    from repro.layers.attention import _flash_bwd
    dq, dk, dv, _, _ = _flash_bwd(causal, window, q_block, kv_block, res,
                                  dout)
    return dq, dk, dv, None, None


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)
