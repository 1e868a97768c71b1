"""Row-gather Pallas TPU kernel (the MoE dispatch/combine primitive).

``gather_rows(src (N, d), idx (M,)) -> (M, d)`` where ``idx[i] == -1``
yields a zero row. This one primitive implements all four MoE data
movements (each is a permutation-with-drops because capacity slots are
unique):

  dispatch fwd    buf[slot]   = x[src_tok]          gather(x, src_row)
  dispatch bwd    dx[t]       = sum_k dbuf[slot]    gather(dbuf, tok_slots) + sum
  combine  fwd    y[t]        = sum_k g yb[slot]    gather(yb, tok_slots) * g + sum
  combine  bwd    dyb[slot]   = g dy[src_tok]       gather(dy, src_row) * g

TPU-native design: the row index array rides in scalar-prefetch (SMEM) so
each grid step can issue a dynamic-slice DMA from the source (kept in
ANY/HBM memory space) into its VMEM output block — the canonical TPU
sparse-row-copy pattern (same shape as embedding gathers / megablocks
dispatch). The MXU is not involved; the kernel is a DMA engine, which is
exactly why the XLA scatter/gather lowering (and its f32-promoted
scatter-add transpose) is worth replacing on the target.

The TPU tiles the last two dimensions of a memory reference by (8, 128),
and a DMA may not cut a tile. Rows therefore travel as ``(N, 1, d)``
(one row per untiled leading index) of 32-bit words: packed dtypes are
bitcast to ``uint32`` on the way in and back on the way out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(idx_ref, src_ref, out_ref, sem, *, block_rows: int):
    """One grid step DMAs ``block_rows`` source rows from HBM into the out
    block, then zeroes the rows whose index is -1."""
    base = pl.program_id(0) * block_rows

    def row_copy(i):
        r = jnp.maximum(idx_ref[base + i], 0)  # -1 copies row 0, zeroed below
        return pltpu.make_async_copy(
            src_ref.at[pl.ds(r, 1)], out_ref.at[pl.ds(i, 1)], sem
        )

    for i in range(block_rows):  # static unroll: all copies in flight
        row_copy(i).start()
    for i in range(block_rows):
        row_copy(i).wait()
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_rows, 1, 1), 0)
    keep = jnp.zeros((block_rows, 1, 1), jnp.bool_)
    for i in range(block_rows):
        keep = keep | ((rows == i) & (idx_ref[base + i] >= 0))
    out_ref[...] = jnp.where(keep, out_ref[...], 0).astype(out_ref.dtype)


def gather_rows_pallas(
    src: jnp.ndarray,  # (N, d)
    idx: jnp.ndarray,  # (M,) int32, -1 -> zero row
    *,
    block_rows: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    N, d = src.shape
    (M,) = idx.shape
    # row DMAs need 32-bit rows: packed dtypes (bf16, int8, ...) travel
    # as uint32 words and are bitcast back afterwards
    pack = 4 // src.dtype.itemsize
    assert d % pack == 0, (d, src.dtype)
    words = src if pack == 1 else jax.lax.bitcast_convert_type(
        src.reshape(N, d // pack, pack), jnp.uint32
    )
    words = words.reshape(N, 1, d // pack)
    pad = (-M) % block_rows
    idx_p = jnp.pad(idx, (0, pad), constant_values=-1)
    grid = (idx_p.shape[0] // block_rows,)
    out = pl.pallas_call(
        functools.partial(_gather_kernel, block_rows=block_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # idx rides in SMEM
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # src in HBM
            out_specs=pl.BlockSpec(
                (block_rows, 1, d // pack), lambda i, idx_ref: (i, 0, 0)
            ),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (idx_p.shape[0], 1, d // pack), words.dtype
        ),
        interpret=interpret,
    )(idx_p, words)[:M, 0]
    if pack == 1:
        return out
    return jax.lax.bitcast_convert_type(out, src.dtype).reshape(M, d)
