"""Pallas TPU kernel: descriptor-driven weighted scatter-add (dComm combine).

Combine-side descriptor interpretation: expert outputs land back in slot
order; each row is multiplied by its gate weight and accumulated at the
original token row.  One d-column block of the destination stays in VMEM as
an f32 accumulator while the row axis sweeps every input row, so a
destination revisited by any number of (non-consecutive) rows accumulates
in place; the block is written to HBM once, after its last row.

Grid: (d / block_d, rows_in / block_r), row axis innermost and sequential.
dst[i] = -1 rows are dropped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.segment_gather import resident_block_d


def _scatter_kernel(dst_ref, gate_ref, src_ref, out_ref, acc, *, block_r):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    base = j * block_r
    x = src_ref[...].astype(jnp.float32)
    for k in range(block_r):
        t = dst_ref[base + k]

        @pl.when(t >= 0)
        def _add():
            acc[pl.ds(t, 1), :] += gate_ref[base + k] * x[k:k + 1, :]

    @pl.when(j == pl.num_programs(1) - 1)
    def _out():
        out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_rows", "block_r",
                                             "interpret"))
def segment_scatter_add(src: jax.Array, dst: jax.Array, gates: jax.Array,
                        out_rows: int, *, block_r: int = 32,
                        interpret: bool = False) -> jax.Array:
    """out[dst[i]] += gates[i] * src[i].  src: (R, d); dst/gates: (R,)."""
    r, d = src.shape
    rp = -(-r // block_r) * block_r
    pad = rp - r
    dst = jnp.pad(dst.astype(jnp.int32), (0, pad), constant_values=-1)
    gates = jnp.pad(gates.astype(jnp.float32), (0, pad))
    src = jnp.pad(src, ((0, pad), (0, 0)))
    bd, vmem = resident_block_d(out_rows, d, src.dtype.itemsize)

    fn = pl.pallas_call(
        functools.partial(_scatter_kernel, block_r=block_r),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                  # dst, gates
            grid=(d // bd, rp // block_r),
            in_specs=[pl.BlockSpec((block_r, bd),
                                   lambda i, j, dst, g: (j, i))],
            out_specs=pl.BlockSpec((out_rows, bd),
                                   lambda i, j, dst, g: (0, i)),
            scratch_shapes=[pltpu.VMEM((out_rows, bd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((out_rows, d), src.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="segment_scatter_add",
    )
    return fn(dst, gates, src)
