"""Public jit'd wrappers for the Pallas kernels.

Each op dispatches to the Pallas kernel on TPU (or in interpret mode on
CPU, which executes the kernel body in Python — used by tests/CI; any other
backend is refused rather than silently interpreted) and pads
inputs to TPU tile alignment (8 sublanes × 128 lanes for f32; the wrappers
round up to multiples that work for all supported dtypes).  The pure-jnp
oracles live in ref.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.coded_matvec import coded_matvec_pallas
from repro.kernels.lstm_cell import lstm_cell_pallas
from repro.kernels.mds_decode import mds_decode_pallas
from repro.kernels.mds_encode import mds_encode_pallas

__all__ = ["coded_matvec", "chunk_matvec", "row_block", "mds_encode",
           "mds_decode", "lstm_cell", "interpret_default", "MAX_BLOCK_ROWS",
           "MIN_SPLIT_ROWS"]

#: Largest row block ``chunk_matvec`` hands the kernel.  A (1024, 512) f32
#: tile double-buffered plus its accumulator stays well inside the 16 MiB
#: scoped VMEM of a v5e core; a 4096-row block does not.
MAX_BLOCK_ROWS = 1024

#: Smallest block a call of more than :data:`MAX_BLOCK_ROWS` rows is split
#: into to leave no row padded, so that such a call is never walked in
#: many small blocks, each a grid step of fixed cost.
MIN_SPLIT_ROWS = 256


def interpret_default() -> bool:
    """Native Pallas on TPU, interpret mode on CPU (tests), else an error.

    A GPU or any other backend would otherwise run the kernels through the
    Python interpreter and report a device it never used.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run natively on TPU and in interpret "
                       f"mode on CPU only; the default backend is {backend!r}")


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# coded_matvec
# ---------------------------------------------------------------------------

def coded_matvec(a: jax.Array, x: jax.Array, block_ids: jax.Array,
                 block_rows: int, d_tile: int = 512,
                 interpret: bool | None = None) -> jax.Array:
    """Slack-squeeze coded product: out[i] = A[block_ids[i]·br:(…+1)·br] @ x.

    a: (rows, d); x: (d,) or (d, nvec); block_ids: (nb,) int32.
    Returns (nb, block_rows) for vector x, else (nb, block_rows, nvec).
    Pads d and nvec to tile alignment internally.
    """
    interpret = interpret_default() if interpret is None else interpret
    squeeze = x.ndim == 1
    x2 = x[:, None] if squeeze else x
    rows, d = a.shape
    nvec = x2.shape[1]
    # pad contraction dim to a multiple of d_tile (zeros don't change result)
    d_pad = _round_up(d, min(d_tile, _round_up(d, 128)))
    d_tile = min(d_tile, d_pad)
    nvec_pad = _round_up(nvec, 128)
    a_p = jnp.pad(a, ((0, 0), (0, d_pad - d)))
    x_p = jnp.pad(x2, ((0, d_pad - d), (0, nvec_pad - nvec)))
    out = coded_matvec_pallas(a_p, x_p, block_ids, block_rows,
                              d_tile=d_tile, interpret=interpret)
    out = out[:, :, :nvec]
    return out[:, :, 0] if squeeze else out


def row_block(rows: int) -> int:
    """Rows of each block ``chunk_matvec`` walks a call of ``rows`` rows in.

    Up to :data:`MAX_BLOCK_ROWS` rows the call is one block, rounded up to
    8 rows.  A longer call takes the largest multiple of 8 that divides
    ``rows`` and lies between :data:`MIN_SPLIT_ROWS` and
    :data:`MAX_BLOCK_ROWS`, so that no row is padded; only where none does
    is it :data:`MAX_BLOCK_ROWS`, the last block padded.
    """
    if rows <= MAX_BLOCK_ROWS:
        return _round_up(rows, 8)
    for block in range(MAX_BLOCK_ROWS, MIN_SPLIT_ROWS - 1, -8):
        if rows % block == 0:
            return block
    return MAX_BLOCK_ROWS


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def chunk_matvec(shard: jax.Array, x: jax.Array, r0: jax.Array, rows: int,
                 interpret: bool | None = None) -> jax.Array:
    """``shard[r0:r0+rows] @ x`` for one engine chunk, in one kernel call.

    The chunk is walked as blocks of :func:`row_block` rows through the
    kernel's block-id table (rows padded to a whole number of blocks), so
    any chunk size fits the kernel's VMEM.  ``r0`` is traced: one compiled
    program serves every chunk of a shard; ``r0 + rows`` must not exceed
    the shard's rows.  Returns ``(rows,)`` for a vector ``x``, else
    ``(rows, nvec)``.
    """
    block = row_block(rows)
    padded = _round_up(rows, block)
    a = jax.lax.dynamic_slice_in_dim(shard, r0, rows)
    a = jnp.pad(a, ((0, padded - rows), (0, 0)))
    ids = jnp.arange(padded // block, dtype=jnp.int32)
    out = coded_matvec(a, x, ids, block, interpret=interpret)
    return out.reshape((padded,) + out.shape[2:])[:rows]


# ---------------------------------------------------------------------------
# mds_encode
# ---------------------------------------------------------------------------

def mds_encode(g: jax.Array, blocks: jax.Array, row_tile: int = 256,
               d_tile: int = 512, interpret: bool | None = None) -> jax.Array:
    """g: (n, k); blocks: (k, rows, d) -> (n, rows, d)."""
    interpret = interpret_default() if interpret is None else interpret
    k, rows, d = blocks.shape
    rt = min(row_tile, _round_up(rows, 8))
    dt = min(d_tile, _round_up(d, 128))
    rows_p, d_p = _round_up(rows, rt), _round_up(d, dt)
    blocks_p = jnp.pad(blocks, ((0, 0), (0, rows_p - rows), (0, d_p - d)))
    out = mds_encode_pallas(g, blocks_p, row_tile=rt, d_tile=dt,
                            interpret=interpret)
    return out[:, :rows, :d]


# ---------------------------------------------------------------------------
# mds_decode
# ---------------------------------------------------------------------------

def mds_decode(w: jax.Array, y: jax.Array, r_tile: int = 512,
               interpret: bool | None = None) -> jax.Array:
    """w: (chunks, k, m); y: (chunks, m, r) -> (chunks, k, r)."""
    interpret = interpret_default() if interpret is None else interpret
    chunks, k, m = w.shape
    r = y.shape[2]
    rt = min(r_tile, _round_up(r, 128))
    r_p = _round_up(r, rt)
    y_p = jnp.pad(y, ((0, 0), (0, 0), (0, r_p - r)))
    out = mds_decode_pallas(w, y_p, r_tile=rt, interpret=interpret)
    return out[:, :, :r]


# ---------------------------------------------------------------------------
# lstm_cell
# ---------------------------------------------------------------------------

def lstm_cell(x: jax.Array, h: jax.Array, c: jax.Array, w_ih: jax.Array,
              w_hh: jax.Array, b: jax.Array,
              interpret: bool | None = None):
    """Fused LSTM cell; shapes as in ref.lstm_cell_ref.  Pads B/I/H to tiles.

    Padding note: H is padded per-gate (the packed 4H axis must stay
    gate-aligned), and padded hidden columns produce sigmoid(0)/tanh(0)
    garbage that is sliced off before returning — the real lanes are exact.
    """
    interpret = interpret_default() if interpret is None else interpret
    bsz, idim = x.shape
    hdim = h.shape[1]
    b_p = _round_up(bsz, 8)
    i_p = _round_up(idim, 128)
    h_p = _round_up(hdim, 128)

    x_ = jnp.pad(x, ((0, b_p - bsz), (0, i_p - idim)))
    h_ = jnp.pad(h, ((0, b_p - bsz), (0, h_p - hdim)))
    c_ = jnp.pad(c, ((0, b_p - bsz), (0, h_p - hdim)))
    # repack gate weights: (4H, I) -> 4 × (H, I) -> padded (4H_p, I_p)
    wih4 = w_ih.reshape(4, hdim, idim)
    whh4 = w_hh.reshape(4, hdim, hdim)
    b4 = b.reshape(4, hdim)
    wih_ = jnp.pad(wih4, ((0, 0), (0, h_p - hdim), (0, i_p - idim))
                   ).reshape(4 * h_p, i_p)
    whh_ = jnp.pad(whh4, ((0, 0), (0, h_p - hdim), (0, h_p - hdim))
                   ).reshape(4 * h_p, h_p)
    b_ = jnp.pad(b4, ((0, 0), (0, h_p - hdim))).reshape(4 * h_p)
    h_new, c_new = lstm_cell_pallas(x_, h_, c_, wih_, whh_, b_,
                                    interpret=interpret)
    return h_new[:bsz, :hdim], c_new[:bsz, :hdim]
