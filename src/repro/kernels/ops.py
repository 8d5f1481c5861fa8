"""Jit'd public wrappers over the Pallas kernels.

On TPU the Pallas (Mosaic) path always runs natively: there is no switch to
the jnp references there, and ``REPRO_USE_PALLAS=0`` is refused.  On CPU the
references run by default, and ``REPRO_USE_PALLAS=1`` runs the kernels in
``interpret=True`` (the kernel body evaluated op-by-op — correctness
validation only).  The dense_fused
dComm engines route their staging copies through :func:`segment_gather` /
:func:`segment_scatter_add`, the expert FFN through :func:`fused_swiglu`,
and the tx-island attention core through :func:`flash_attention` —
``use_pallas()`` picks the path at call time.

Every staging wrapper carries a custom VJP so the kernel-routed engines stay
differentiable: gather and scatter-add are each other's transpose (the
backward is itself kernel-routed), and the fused SwiGLU backward recomputes
its hidden activations flash-style (O(C·d) residuals, never the (C, f)
intermediates).

``backend()`` is resolved per call, NOT cached: platform/distributed init may
flip the default backend after import, and tests toggle ``REPRO_USE_PALLAS``
between calls — a cached answer made both silently stale.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.fused_staging import fused_swiglu_pallas as _swiglu_pallas
from repro.kernels.grouped_matmul import grouped_matmul as _gmm_pallas
from repro.kernels.segment_gather import segment_gather as _gather_pallas
from repro.kernels.segment_scatter_add import (
    segment_scatter_add as _scatter_pallas)


def backend() -> str:
    return jax.default_backend()


def use_pallas() -> bool:
    env = os.environ.get("REPRO_USE_PALLAS")
    off = env is not None and env in ("0", "false", "")
    if backend() == "tpu":
        if off:
            raise RuntimeError(
                f"REPRO_USE_PALLAS={env!r} on a TPU: the Pallas kernels are "
                "the TPU path; the jnp references are CPU test oracles")
        return True
    return env is not None and not off


def _interpret() -> bool:
    return backend() == "cpu"


# ------------------------------------------------------- descriptor copies --

@jax.custom_vjp
def segment_gather(src, idx):
    """out[i] = src[idx[i]]; idx == -1 -> zeros.  src: (T, d); idx: (R,).
    VJP: the transpose scatter-add of the cotangent (unit gates)."""
    if use_pallas():
        return _gather_pallas(src, idx, interpret=_interpret())
    return ref.segment_gather_ref(src, idx)


def _gather_fwd(src, idx):
    return segment_gather(src, idx), (src.shape[0], idx)


def _gather_bwd(res, dout):
    n, idx = res
    ones = jnp.ones(idx.shape, jnp.float32)
    return segment_scatter_add(dout, idx, ones, n), None


segment_gather.defvjp(_gather_fwd, _gather_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def segment_scatter_add(src, dst, gates, out_rows: int):
    """out[dst[i]] += gates[i] * src[i]; dst == -1 dropped.  VJP: the
    transpose gather of the cotangent times the gates, plus per-row dgates."""
    if use_pallas():
        return _scatter_pallas(src, dst, gates, out_rows,
                               interpret=_interpret())
    return ref.segment_scatter_add_ref(src, dst, gates, out_rows)


def _scatter_fwd(src, dst, gates, out_rows: int):
    return segment_scatter_add(src, dst, gates, out_rows), (src, dst, gates)


def _scatter_bwd(out_rows, res, dout):
    src, dst, gates = res
    back = segment_gather(dout, dst)                     # (R, d) cotangents
    dsrc = (back.astype(jnp.float32)
            * gates.astype(jnp.float32)[:, None]).astype(src.dtype)
    dgates = jnp.sum(back.astype(jnp.float32) * src.astype(jnp.float32),
                     axis=1).astype(gates.dtype)
    return dsrc, None, dgates


segment_scatter_add.defvjp(_scatter_fwd, _scatter_bwd)


# ------------------------------------------------------- grouped expert FFN --

def grouped_matmul(x, w, counts):
    """(G, C, d) x (G, d, f) per-group matmul, rows >= counts[g] zeroed.
    Forward-only building block; the engines use :func:`fused_swiglu`."""
    if use_pallas():
        return _gmm_pallas(x, w, counts, interpret=_interpret())
    return ref.grouped_matmul_ref(x, w, counts)


def _fused_swiglu_impl(x, w1, w3, w2, counts):
    if use_pallas():
        return _swiglu_pallas(x, w1, w3, w2, counts, interpret=_interpret())
    return ref.fused_swiglu_ref(x, w1, w3, w2, counts)


@jax.custom_vjp
def _fused_swiglu_vjp(x, w1, w3, w2, counts):
    return _fused_swiglu_impl(x, w1, w3, w2, counts)


def _fused_swiglu_fwd(x, w1, w3, w2, counts):
    return _fused_swiglu_impl(x, w1, w3, w2, counts), (x, w1, w3, w2, counts)


def _fused_swiglu_bwd(res, dy):
    x, w1, w3, w2, counts = res
    live = (counts[..., None] > jnp.arange(x.shape[2]))[..., None]
    dyf = jnp.where(live, dy, 0).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    w1f, w3f, w2f = (w.astype(jnp.float32) for w in (w1, w3, w2))
    h = jnp.einsum("secd,edf->secf", xf, w1f)
    u = jnp.einsum("secd,edf->secf", xf, w3f)
    sg = jax.nn.sigmoid(h)
    sh = h * sg                                          # silu(h)
    da = jnp.einsum("secd,efd->secf", dyf, w2f)
    dw2 = jnp.einsum("secf,secd->efd", sh * u, dyf)
    du = da * sh
    dh = da * u * (sg * (1.0 + h * (1.0 - sg)))          # d silu
    dx = (jnp.einsum("secf,edf->secd", dh, w1f)
          + jnp.einsum("secf,edf->secd", du, w3f))
    dw1 = jnp.einsum("secd,secf->edf", xf, dh)
    dw3 = jnp.einsum("secd,secf->edf", xf, du)
    return (dx.astype(x.dtype), dw1.astype(w1.dtype), dw3.astype(w3.dtype),
            dw2.astype(w2.dtype), None)


_fused_swiglu_vjp.defvjp(_fused_swiglu_fwd, _fused_swiglu_bwd)


def fused_swiglu(x, w1, w3, w2, counts=None):
    """Grouped SwiGLU over the landed buffer: silu(x@w1) * (x@w3) @ w2 per
    (source-lane, local-expert) group, one fused Pallas kernel when
    ``use_pallas()`` (no HBM round-trip of the (C, f) hidden activations).

    x: (S, E, C, d); w1/w3: (E, d, f); w2: (E, f, d); counts: (S, E)
    occupancy or None (all rows live — padding rows are zero and SwiGLU maps
    zero rows to zero, so landing-side counts are optional).  Differentiable
    (custom VJP, flash-style recompute).
    """
    if counts is None:
        counts = jnp.full(x.shape[:2], x.shape[2], jnp.int32)
    return _fused_swiglu_vjp(x, w1, w3, w2, counts)


# ------------------------------------------------------- island attention --

def flash_attention(q, k, v, q_positions, k_positions, causal=True,
                    window=None, q_block=512, kv_block=512):
    """Position-safe block-skipping flash attention: the Pallas kernel when
    ``use_pallas()``, else the lax flash.  Both mask from the actual
    positions and skip from per-block position bounds, so shifted island
    chunks are handled correctly by either path."""
    if use_pallas():
        from repro.kernels.flash_attention import flash_attention as _pallas
        return _pallas(q, k, v, q_positions, k_positions, causal, window,
                       q_block, kv_block, _interpret())
    from repro.layers.attention import flash_attention as _lax
    return _lax(q, k, v, q_positions, k_positions, causal, window,
                q_block, kv_block)
