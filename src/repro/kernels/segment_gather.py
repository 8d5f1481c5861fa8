"""Pallas TPU kernel: descriptor-driven row gather (dComm dispatch copy).

The paper's CUDA copy engine interprets segment descriptors inline with the
transfer.  On TPU the analogue is a scalar-prefetched gather: one d-column
block of the source sits in VMEM for the whole sweep over the output rows,
and each output row reads its source row at the descriptor's index, so rows
land already in communication-buffer order with no intermediate
materialisation.  Used to stage tokens into the dense_fused engine's send
buffer (slot layout), fusing the paper's "rearrangement" into the copy.

Grid: (d / block_d, rows_out / block_r); the row axis is innermost, so the
source block is loaded (and widened to f32, whose rows can be addressed
one at a time) once per column block.  Invalid descriptors (-1: empty
slot) write zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_BUDGET = 24 << 20


def resident_block_d(rows: int, d: int, itemsize: int) -> tuple[int, int]:
    """Widest 128-multiple column block of a (rows, d) array that can sit in
    VMEM whole, double-buffered in its own dtype plus one f32 copy; returns
    (block_d, the VMEM limit to request for it)."""
    per_col = rows * (2 * itemsize + 4)
    cands = [b for b in range(128, d + 1, 128) if d % b == 0] or [d]
    fit = [b for b in cands if b * per_col <= _VMEM_BUDGET]
    if not fit:
        raise ValueError(f"{rows} resident rows do not fit VMEM even at "
                         f"block_d={cands[0]}")
    bd = max(fit)
    return bd, max(32 << 20, bd * per_col + (8 << 20))


def _gather_kernel(idx_ref, src_ref, out_ref, src32, rows32, *, block_r):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _load():
        src32[...] = src_ref[...].astype(jnp.float32)

    base = j * block_r
    for k in range(block_r):
        row = idx_ref[base + k]
        got = src32[pl.ds(jnp.maximum(row, 0), 1), :]
        rows32[k:k + 1, :] = jnp.where(row >= 0, got, 0.0)
    out_ref[...] = rows32[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def segment_gather(src: jax.Array, idx: jax.Array, *, block_r: int = 32,
                   interpret: bool = False) -> jax.Array:
    """out[i] = src[idx[i]] (idx -1 -> zeros).  src: (T, d); idx: (R,)."""
    t, d = src.shape
    r = idx.shape[0]
    rp = -(-r // block_r) * block_r
    idx = jnp.pad(idx.astype(jnp.int32), (0, rp - r), constant_values=-1)
    bd, vmem = resident_block_d(t, d, src.dtype.itemsize)

    fn = pl.pallas_call(
        functools.partial(_gather_kernel, block_r=block_r),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(d // bd, rp // block_r),
            in_specs=[pl.BlockSpec((t, bd), lambda i, j, idx_ref: (0, i))],
            out_specs=pl.BlockSpec((block_r, bd),
                                   lambda i, j, idx_ref: (j, i)),
            scratch_shapes=[pltpu.VMEM((t, bd), jnp.float32),
                            pltpu.VMEM((block_r, bd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rp, d), src.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="segment_gather",
    )
    out = fn(idx, src)
    return out if rp == r else out[:r]
