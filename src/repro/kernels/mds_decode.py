"""Per-chunk MDS decode kernel: out[c] = W[c] @ Y[c].

After an S²C² round the master holds, for every chunk index c, the partial
products of the ≥k workers that computed c, stacked as Y: (chunks, m, r),
plus precomputed decode weights W: (chunks, k, m) (rows of the inverted
generator submatrix, zero columns for non-covering workers).  Decoding is a
batched small matmul — tiny contraction (m ≤ n ≤ 32) over a large r, i.e.
bandwidth-bound streaming, fused here into one pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["mds_decode_pallas"]


def _kernel(w_ref, y_ref, o_ref):
    """w_ref: (1, k, m); y_ref: (1, m, tr); o_ref: (1, k, tr)."""
    w = w_ref[0, :, :].astype(jnp.float32)
    y = y_ref[0, :, :].astype(jnp.float32)
    o_ref[0, :, :] = jnp.dot(w, y, precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("r_tile", "interpret"))
def mds_decode_pallas(w: jax.Array, y: jax.Array, r_tile: int = 512,
                      interpret: bool = False) -> jax.Array:
    """w: (chunks, k, m); y: (chunks, m, r) -> (chunks, k, r)."""
    chunks, k, m = w.shape
    c_y, m_y, r = y.shape
    assert chunks == c_y and m == m_y, (w.shape, y.shape)
    if r % r_tile:
        raise ValueError(f"r={r} must tile by r_tile={r_tile}")
    grid = (chunks, r // r_tile)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, k, m), lambda c, j: (c, 0, 0)),
            pl.BlockSpec((1, m, r_tile), lambda c, j: (c, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, k, r_tile), lambda c, j: (c, 0, j)),
        out_shape=jax.ShapeDtypeStruct((chunks, k, r), y.dtype),
        interpret=interpret,
    )(w, y)
    return out
