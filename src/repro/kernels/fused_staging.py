"""Pallas TPU kernel: fused grouped SwiGLU over the landed dispatch buffer.

The middle link of the fused dispatch-stage chain

    segment_gather  ->  grouped SwiGLU (this kernel)  ->  segment_scatter_add

that the dense_fused engines route their staging through when
``kernels.ops.use_pallas()`` is on.  The whole expert FFN —
``silu(x @ w1) * (x @ w3) @ w2`` per (source-lane, local-expert) group — runs
in ONE ``pallas_call``: for each f-block the gate/up projections and the SiLU
product live only in VMEM and are immediately contracted into an f32 (bc, d)
output accumulator, so the (C, f) hidden activations are never materialised
in HBM between the matmuls (the FUSCO transformation-fusion property applied
*inside* the slice).

Extends ``grouped_matmul``'s scalar-prefetched occupancy skipping: group
occupancy counts skip whole row-blocks of MXU work, and the output write
masks rows >= counts row-granularly.  ``counts=None`` means every row is
live — the flat engines only know sender-side occupancy, and their padding
rows are zero (zero rows produce zero output through SwiGLU, and gates drop
them at combine), so correctness does not depend on landing-side counts.

Grid: (S, E, C/block_c, f/block_f); f is the contraction-accumulation axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _divisor_block(n: int, target: int) -> int:
    """Largest block size <= target that divides n (shapes are static)."""
    for b in range(min(target, n), 0, -1):
        if n % b == 0:
            return b
    return 1


def _swiglu_kernel(counts_ref, x_ref, w1_ref, w3_ref, w2_ref, out_ref,
                   acc_ref, *, block_c):
    si = pl.program_id(0)
    ei = pl.program_id(1)
    ci = pl.program_id(2)
    fi = pl.program_id(3)
    nf = pl.num_programs(3)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip MXU work for row-blocks entirely beyond this group's occupancy
    occupied = counts_ref[si, ei] > ci * block_c

    @pl.when(occupied)
    def _mm():
        x = x_ref[0, 0]                                    # (bc, d)
        h = jnp.dot(x, w1_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, w3_ref[0], preferred_element_type=jnp.float32)
        a = (h * jax.lax.logistic(h)) * u                  # SiLU in f32, VMEM
        acc_ref[...] += jnp.dot(a, w2_ref[0].astype(jnp.float32),
                                preferred_element_type=jnp.float32)

    @pl.when(fi == nf - 1)
    def _out():
        # row-granular occupancy mask (same contract as grouped_matmul)
        rows = ci * block_c + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        live = rows < counts_ref[si, ei]
        out_ref[0, 0] = jnp.where(live, acc_ref[...], 0.0).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_f", "interpret"))
def fused_swiglu_pallas(x: jax.Array, w1: jax.Array, w3: jax.Array,
                        w2: jax.Array, counts: jax.Array, *,
                        block_c: int = 128, block_f: int = 128,
                        interpret: bool = False) -> jax.Array:
    """x: (S, E, C, d) landed rows; w1/w3: (E, d, f); w2: (E, f, d);
    counts: (S, E) group occupancy.  Returns (S, E, C, d) expert outputs with
    rows >= counts zeroed.  Differentiate via ``kernels.ops.fused_swiglu``
    (custom VJP); this raw entry is forward-only."""
    s, e, c, d = x.shape
    _, _, f = w1.shape
    bc = _divisor_block(c, block_c)
    bf = _divisor_block(f, block_f)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                    # counts
        grid=(s, e, c // bc, f // bf),
        in_specs=[
            pl.BlockSpec((1, 1, bc, d),
                         lambda si, ei, ci, fi, cnt: (si, ei, ci, 0)),
            pl.BlockSpec((1, d, bf), lambda si, ei, ci, fi, cnt: (ei, 0, fi)),
            pl.BlockSpec((1, d, bf), lambda si, ei, ci, fi, cnt: (ei, 0, fi)),
            pl.BlockSpec((1, bf, d), lambda si, ei, ci, fi, cnt: (ei, fi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bc, d),
                               lambda si, ei, ci, fi, cnt: (si, ei, ci, 0)),
        scratch_shapes=[pltpu.VMEM((bc, d), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_swiglu_kernel, block_c=bc),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, e, c, d), x.dtype),
        interpret=interpret,
        name="fused_swiglu_pallas",
    )
    return fn(counts.astype(jnp.int32), x, w1, w3, w2)
