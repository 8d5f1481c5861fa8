"""Pallas TPU kernel: grouped expert matmul over the landed dispatch buffer.

Consumes the dense_fused engine's landed layout (G groups × C capacity rows ×
d) IN PLACE — each group's rows multiply that group's expert weight — so the
expert FFN needs no post-communication rearrangement (the FUSCO property).
Group occupancy counts are scalar-prefetched; fully-empty row-blocks skip the
MXU work, and rows at positions >= counts[g] inside partially occupied blocks
are masked to zero at the output write (row-granular contract — padding rows
never leak garbage downstream).

Grid: (G, C/block_c, f/block_f, d/block_d) with an f32 VMEM accumulator over
the contraction dimension.  Block sizes default to MXU-aligned 128 multiples.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(counts_ref, x_ref, w_ref, out_ref, acc_ref, *, block_c):
    g = pl.program_id(0)
    ci = pl.program_id(1)
    k = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip MXU work for row-blocks beyond this group's occupancy
    occupied = counts_ref[g] > ci * block_c

    @pl.when(occupied)
    def _mm():
        acc_ref[...] += jnp.dot(x_ref[0], w_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _out():
        # row-granular occupancy mask: rows >= counts[g] are dead padding in
        # the landed layout and must write zeros, not stale matmul output
        rows = ci * block_c + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        live = rows < counts_ref[g]
        out_ref[0] = jnp.where(live, acc_ref[...], 0.0).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_f", "block_d",
                                    "interpret"))
def grouped_matmul(x: jax.Array, w: jax.Array, counts: jax.Array, *,
                   block_c: int = 128, block_f: int = 128,
                   block_d: int = 128, interpret: bool = False) -> jax.Array:
    """x: (G, C, d) grouped rows; w: (G, d, f); counts: (G,) occupancy.

    Returns (G, C, f) = x @ w per group; rows at positions >= counts[g]
    (padding) are zero — row-granular, including inside partially occupied
    blocks.
    """
    g, c, d = x.shape
    _, _, f = w.shape
    bc, bf, bd = min(block_c, c), min(block_f, f), min(block_d, d)
    assert c % bc == 0 and f % bf == 0 and d % bd == 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                  # counts
        grid=(g, c // bc, f // bf, d // bd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda gi, ci, fi, ki, cnt: (gi, ci, ki)),
            pl.BlockSpec((1, bd, bf), lambda gi, ci, fi, ki, cnt: (gi, ki, fi)),
        ],
        out_specs=pl.BlockSpec(
            (1, bc, bf), lambda gi, ci, fi, ki, cnt: (gi, ci, fi)),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_gmm_kernel, block_c=bc),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, c, f), x.dtype),
        interpret=interpret,
        name="grouped_matmul",
    )
    return fn(counts.astype(jnp.int32), x, w)
