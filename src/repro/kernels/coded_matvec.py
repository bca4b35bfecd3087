"""Slack-squeeze coded matmul kernel — the paper's partial-work idea, TPU-native.

The S²C² scheduler assigns each worker a subset of the row-blocks of its
coded partition.  On a VM cluster "partial work" means the worker's loop
stops early; on a TPU the analogue is **grid-level work skipping**: the
kernel grid is sized to the number of *assigned* blocks, and a scalar-
prefetched index table maps grid step → HBM row-block.  Unassigned blocks
are never touched: no HBM→VMEM DMA, no MXU cycles — the compute and memory
cost both scale with ``len(block_ids)`` exactly like the paper's per-worker
latency scales with assigned rows.

Tiling: row-blocks of ``block_rows`` rows (the S²C² chunk) stream through
VMEM tiles of (block_rows, d_tile); the inner grid dimension walks the
contraction dim, accumulating into a float32 VMEM scratch so the MXU sees
aligned (8×128-multiple) operands regardless of dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

__all__ = ["coded_matvec_pallas"]


def _kernel(ids_ref, a_ref, x_ref, o_ref, acc_ref, *, n_dtiles: int):
    """One (assigned-block, d-tile) grid step.

    ids_ref : prefetched (nb,) int32 — assigned block ids (used by index_map)
    a_ref   : (block_rows, d_tile) VMEM tile of the selected row-block
    x_ref   : (d_tile, nvec) VMEM tile of the input vectors
    o_ref   : (1, block_rows, nvec) output tile (written on the last d-tile)
    acc_ref : (block_rows, nvec) float32 VMEM accumulator scratch
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # f32 operands get full-f32 MXU passes (the TPU default rounds them to
    # bf16, which misses a 1e-3 relative error bound at d = 8192); Mosaic
    # accepts that contract precision for f32 operands only
    f32 = a_ref.dtype == jnp.float32 and x_ref.dtype == jnp.float32
    precision = jax.lax.Precision.HIGHEST if f32 else None
    acc_ref[...] += jnp.dot(a_ref[...], x_ref[...], precision=precision,
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_dtiles - 1)
    def _emit():
        o_ref[0, :, :] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "d_tile", "interpret"))
def coded_matvec_pallas(a: jax.Array, x: jax.Array, block_ids: jax.Array,
                        block_rows: int, d_tile: int = 512,
                        interpret: bool = False) -> jax.Array:
    """Compute compacted products out[i] = A[block_ids[i]] @ x.

    a: (rows, d) coded partition (rows = chunks·block_rows, d % d_tile == 0)
    x: (d, nvec)
    block_ids: (nb,) int32 — assigned block indices; nb is static.
    Returns (nb, block_rows, nvec).
    """
    rows, d = a.shape
    d_x, nvec = x.shape
    assert d == d_x, (d, d_x)
    assert rows % block_rows == 0, (rows, block_rows)
    if d % d_tile:
        raise ValueError(f"d={d} not divisible by d_tile={d_tile}")
    nb = block_ids.shape[0]
    n_dtiles = d // d_tile

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, n_dtiles),
        in_specs=[
            # A tile: row-block chosen by the prefetched assignment table.
            pl.BlockSpec((block_rows, d_tile), lambda i, j, ids: (ids[i], j)),
            # x tile: walks the contraction dim, shared across blocks.
            pl.BlockSpec((d_tile, nvec), lambda i, j, ids: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_rows, nvec),
                               lambda i, j, ids: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((block_rows, nvec), jnp.float32)],
    )

    out = pl.pallas_call(
        functools.partial(_kernel, n_dtiles=n_dtiles),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, block_rows, nvec), x.dtype),
        interpret=interpret,
    )(block_ids, a, x)
    return out
