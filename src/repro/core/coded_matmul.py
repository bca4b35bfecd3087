"""Distributed coded matrix–vector/matrix multiplication via shard_map.

This is the *device* realization of the paper's master/worker runtime on a
JAX mesh: the coded partitions live sharded over a ``workers`` mesh axis
(encode once — the paper's zero-data-movement property), and every
iteration applies a fresh S²C² allocation without relayout:

  1. host: predict speeds → ``general_allocation`` → (begin, count) +
     per-chunk decode weights (``MDSCode.chunk_decode_weights``);
  2. device (shard_map over ``workers``): each worker computes only its
     assigned cyclic chunk range of ``Ã_w · x`` — masked compute, or the
     Pallas ``coded_matvec`` kernel which skips unassigned blocks entirely;
  3. device: results are combined with the decode weights via one
     reduce-scatter/all-gather — the decode is a small matmul, fused into
     the collective epilogue.

The SPMD program is identical across allocations (only the integer tables
change), so one compiled executable serves every iteration — re-planning
costs zero recompilation.  This mirrors how the paper's master re-plans
every iteration without touching the data distribution.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.coding import MDSCode
from repro.core.s2c2 import Allocation

__all__ = ["CodedMatvec", "coded_partition_shards", "masked_partial_products"]


def coded_partition_shards(code: MDSCode, a: jax.Array) -> jax.Array:
    """Encode A into (n, D/k, d) stacked coded partitions (host-side, once)."""
    return code.encode(a)


def _chunk_mask(begin: jax.Array, count: jax.Array, chunks: int) -> jax.Array:
    idx = jnp.arange(chunks)
    rel = (idx - begin) % chunks
    return rel < count


def masked_partial_products(coded: jax.Array, x: jax.Array, begin: jax.Array,
                            count: jax.Array, chunks: int) -> jax.Array:
    """Reference (non-Pallas) per-worker partial product with chunk masking.

    coded: (rows, d) this worker's partition; rows % chunks == 0.
    Returns (chunks, rows_per_chunk): y[c] = coded_chunk_c @ x if assigned
    else 0.  The Pallas kernel (`repro.kernels.coded_matvec`) computes the
    same thing while *skipping* unassigned chunks' HBM traffic.
    """
    rows, d = coded.shape
    rpc = rows // chunks
    mask = _chunk_mask(begin, count, chunks)               # (chunks,)
    # HIGHEST: on TPU the default f32 matmul rounds operands to bf16
    y = jnp.matmul(coded.reshape(chunks, rpc, d), x,
                   precision=jax.lax.Precision.HIGHEST).reshape(chunks, rpc)
    return y * mask[:, None].astype(y.dtype)


@dataclasses.dataclass
class CodedMatvec:
    """(n, k)-MDS coded distributed matvec with per-iteration S²C² planning.

    Usage::

        cm = CodedMatvec(code, chunks=C, mesh=mesh, axis="workers")
        state = cm.shard(A)                  # encode + place, once
        y = cm.apply(state, x, alloc, weights)   # every iteration

    ``apply`` is jit-compiled once; ``alloc``/``weights`` are data.
    """

    code: MDSCode
    chunks: int
    mesh: Mesh
    axis: str = "workers"
    use_pallas: bool = False

    def __post_init__(self):
        if self.mesh.shape[self.axis] != self.code.n:
            raise ValueError(
                f"mesh axis {self.axis!r} has size {self.mesh.shape[self.axis]} "
                f"but code.n={self.code.n}")

    # -- data placement -----------------------------------------------------
    def shard(self, a: jax.Array) -> jax.Array:
        """Encode and shard: (n, D/k, d) with the leading dim on `axis`."""
        coded = self.code.encode(a)
        rows = coded.shape[1]
        if rows % self.chunks:
            pad = (-rows) % self.chunks
            coded = jnp.pad(coded, ((0, 0), (0, pad), (0, 0)))
        sharding = NamedSharding(self.mesh, P(self.axis, None, None))
        return jax.device_put(coded, sharding)

    # -- planning (host) ----------------------------------------------------
    def plan_tables(self, alloc: Allocation):
        """Allocation → device tables: (begin, count, decode_weights).

        decode_weights: (chunks, k, n) float32 — per-chunk decode matrix
        with zero columns for non-covering workers.
        """
        cov = alloc.masks().T                    # (chunks, n)
        w = self.code.chunk_decode_weights(cov)  # validates coverage ≥ k
        return (jnp.asarray(alloc.begin, jnp.int32),
                jnp.asarray(alloc.count, jnp.int32),
                jnp.asarray(w, jnp.float32))

    # -- distributed apply ----------------------------------------------------
    def apply(self, coded: jax.Array, x: jax.Array, begin: jax.Array,
              count: jax.Array, weights: jax.Array) -> jax.Array:
        """Compute A @ x from the coded shards under an S²C² allocation.

        coded: (n, rows, d) sharded on `axis`; x: (d,) replicated;
        begin/count: (n,) int32; weights: (chunks, k, n).
        Returns y: (k * rows,) — the original (padded) product, replicated.
        """
        chunks = self.chunks
        axis = self.axis
        use_pallas = self.use_pallas

        def worker(coded_blk, x_, begin_, count_, weights_):
            # coded_blk: (1, rows, d) — this worker's partition
            w_id = jax.lax.axis_index(axis)
            part = coded_blk[0]
            if use_pallas:
                from repro.kernels.ops import coded_matvec as pallas_matvec
                y = pallas_matvec(part, x_, begin_[w_id], count_[w_id], chunks)
            else:
                y = masked_partial_products(part, x_, begin_[w_id],
                                            count_[w_id], chunks)
            # y: (chunks, rows_per_chunk) this worker's masked partials.
            # Decode: out[c, i, r] = Σ_w weights[c, i, w] * y_w[c, r]
            # realized as a weighted psum — the collective *is* the decoder.
            contrib = weights_[:, :, w_id][:, :, None] * y[:, None, :].astype(jnp.float32)
            return jax.lax.psum(contrib, axis)    # (chunks, k, rpc), replicated

        rows = coded.shape[1]
        dec = jax.shard_map(
            worker, mesh=self.mesh,
            in_specs=(P(self.axis, None, None), P(), P(), P(), P()),
            out_specs=P(),
        )(coded, x, begin, count, weights)
        # dec: (chunks, k, rpc) -> original row order:
        # data block i, chunk c, row r  <-  position i*rows + c*rpc + r.
        y = jnp.swapaxes(dec, 0, 1)               # (k, chunks, rpc)
        return y.reshape(self.code.k * rows).astype(x.dtype)

    def jit_apply(self):
        fn = partial(CodedMatvec.apply, self)
        return jax.jit(fn)


# ---------------------------------------------------------------------------
# Numerically exact single-host oracle (used by tests)
# ---------------------------------------------------------------------------

def oracle_matvec(a: np.ndarray, x: np.ndarray) -> np.ndarray:
    return np.asarray(a, np.float64) @ np.asarray(x, np.float64)
