"""MDS encode kernel: coded partitions from data blocks, C[w] = Σ_i G[w,i]·A[i].

Encoding happens once per dataset (the paper's one-time setup cost), but at
framework scale "once" is a full pass over a multi-GB matrix per host, so
it's worth a kernel: the contraction dim k is tiny (≤ 32) while rows×d is
huge — a perfect streaming op.  We tile (rows, d) through VMEM and keep all
k input blocks' tiles resident per step: VMEM per step = (k+1)·tile bytes.
The output partition w is the innermost grid axis, so the k input tiles
stay in VMEM while all n coded tiles of that (rows, d) window are written.

The generator G is prefetched as a scalar operand into SMEM (it is k·n
floats — it parameterizes the *index-free* linear combination, computed on
the VPU as k scalar-times-tile multiply-adds).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

__all__ = ["mds_encode_pallas"]


def _kernel(g_ref, a_ref, o_ref, *, k: int):
    """g_ref: (n, k) SMEM generator; a_ref: (k, tr, td) tiles of every data
    block; o_ref: (1, tr, td) tile of coded partition w."""
    w = pl.program_id(2)
    acc = g_ref[w, 0] * a_ref[0, :, :].astype(jnp.float32)
    for i in range(1, k):
        acc = acc + g_ref[w, i] * a_ref[i, :, :].astype(jnp.float32)
    o_ref[0, :, :] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("row_tile", "d_tile", "interpret"))
def mds_encode_pallas(g: jax.Array, blocks: jax.Array, row_tile: int = 256,
                      d_tile: int = 512, interpret: bool = False) -> jax.Array:
    """g: (n, k); blocks: (k, rows, d) -> (n, rows, d) coded partitions."""
    n, k = g.shape
    k_b, rows, d = blocks.shape
    assert k == k_b, (k, k_b)
    if rows % row_tile or d % d_tile:
        raise ValueError(f"(rows={rows}, d={d}) must tile by "
                         f"({row_tile}, {d_tile})")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // row_tile, d // d_tile, n),
        in_specs=[pl.BlockSpec((k, row_tile, d_tile),
                               lambda i, j, w, g_: (0, i, j))],
        out_specs=pl.BlockSpec((1, row_tile, d_tile),
                               lambda i, j, w, g_: (w, i, j)),
    )
    # G rounded to the blocks' dtype first (the combination's coefficients
    # are those of the dtype the caller encodes in), then held as f32
    # scalars: SMEM holds 32-bit words
    g32 = g.astype(blocks.dtype).astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, rows, d), blocks.dtype),
        interpret=interpret,
    )(g32, blocks)
