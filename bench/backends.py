"""Compute backends the benchmark puts around or in place of the program's.

* :class:`TimedBackend` delegates to the program's backend, records the
  wall time of every ``compute_chunk`` call, and then stretches the chunk
  by the straggler mix (``bench.stragglers``); while the profiler runs it
  writes ``bench.compute_chunk`` and ``bench.straggler_sleep`` host
  annotations around the two.
* :class:`HighPrecisionControl` is the correctness control: the chunk
  product computed in the precision one step below the configuration's
  (float32 at ``highest``), i.e. ``high``: three bfloat16 passes over the
  shard held as a bfloat16 (hi, lo) pair, accumulated in float32.  It is
  spelled out rather than asked of XLA, so it computes the same on the CPU.
* :class:`FaultyBackend` breaks the timed path underneath on purpose, for
  the tests that see ``correct`` come out false.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np


class TimedBackend:
    """Shard-aware delegating backend that times and stretches each chunk.

    After the inner call, a worker the mix slows sleeps
    ``compute · slowdown`` (:meth:`bench.stragglers.MixInjector.slowdown`),
    so that its chunk lasts ``compute / s``.
    """

    def __init__(self, inner, mix=None):
        self.inner = inner
        self.mix = mix
        self.annotate = False           # set while the profiler traces
        self._lock = threading.Lock()
        # (start, compute end, sleep end or compute end if none, worker,
        # sleep asked) of every call
        self.calls: List[Tuple[float, ...]] = []  # guarded_by: _lock

    def compute_chunk(self, worker_id: int, shard_id: str, shard: np.ndarray,
                      r0: int, r1: int, x: np.ndarray) -> np.ndarray:
        annotate = self.annotate
        if annotate:
            import jax.profiler
            with jax.profiler.TraceAnnotation("bench.compute_chunk"):
                t0 = time.perf_counter()
                y = self.inner.compute_chunk(worker_id, shard_id, shard,
                                             r0, r1, x)
                t1 = time.perf_counter()
        else:
            t0 = time.perf_counter()
            y = self.inner.compute_chunk(worker_id, shard_id, shard, r0, r1, x)
            t1 = time.perf_counter()
        extra = (t1 - t0) * self.mix.slowdown(worker_id) if self.mix else 0.0
        t2 = t1
        if extra > 0.0:
            if annotate:
                with jax.profiler.TraceAnnotation("bench.straggler_sleep"):
                    time.sleep(extra)
            else:
                time.sleep(extra)
            t2 = time.perf_counter()
        with self._lock:
            self.calls.append((t0, t1, t2, worker_id, extra))
        return y

    def drop_shard(self, worker_id: int, shard_id: str) -> None:
        self.inner.drop_shard(worker_id, shard_id)

    def records(self, t_from: float = -np.inf,
                t_to: float = np.inf) -> np.ndarray:
        """``(start, compute end, chunk end, worker, sleep asked)`` rows of
        the calls that started in ``[t_from, t_to)``."""
        with self._lock:
            calls = np.asarray(self.calls, dtype=np.float64).reshape(-1, 5)
        keep = (calls[:, 0] >= t_from) & (calls[:, 0] < t_to)
        return calls[keep]

    def durations(self, t_from: float = -np.inf,
                  t_to: float = np.inf) -> np.ndarray:
        """Compute seconds of the calls that started in ``[t_from, t_to)``."""
        calls = self.records(t_from, t_to)
        return calls[:, 1] - calls[:, 0]


@functools.lru_cache(maxsize=None)
def _high_program(rows: int):
    import jax
    import jax.numpy as jnp

    def dot(a, b):
        return jnp.dot(a, b, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)

    @jax.jit
    def chunk(hi, lo, x_hi, x_lo, r0):
        a_hi = jax.lax.dynamic_slice_in_dim(hi, r0, rows)
        a_lo = jax.lax.dynamic_slice_in_dim(lo, r0, rows)
        return dot(a_hi, x_hi) + (dot(a_hi, x_lo) + dot(a_lo, x_hi))

    return chunk


def split_bf16(a32):
    """``a ≈ hi + lo`` with both halves in bfloat16."""
    import jax.numpy as jnp
    hi = a32.astype(jnp.bfloat16)
    lo = (a32 - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


class HighPrecisionControl:
    """Chunk products at ``high`` (bf16 x3) precision, shards held as a
    bfloat16 (hi, lo) pair on the device."""

    def __init__(self):
        self._lock = threading.Lock()
        self._shards: Dict[Tuple[int, str], tuple] = {}  # guarded_by: _lock

    def _pair(self, worker_id: int, shard_id: str, shard: np.ndarray):
        key = (worker_id, shard_id)
        with self._lock:
            pair = self._shards.get(key)
        if pair is None:
            import jax.numpy as jnp
            pair = split_bf16(jnp.asarray(shard, jnp.float32))
            with self._lock:
                self._shards[key] = pair
        return pair

    def compute_chunk(self, worker_id: int, shard_id: str, shard: np.ndarray,
                      r0: int, r1: int, x: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp
        hi, lo = self._pair(worker_id, shard_id, shard)
        x_hi, x_lo = split_bf16(jnp.asarray(x, jnp.float32))
        y = _high_program(r1 - r0)(hi, lo, x_hi, x_lo, r0)
        return np.asarray(y, dtype=np.float64)

    def drop_shard(self, worker_id: int, shard_id: str) -> None:
        with self._lock:
            self._shards.pop((worker_id, shard_id), None)


#: faults a cell of this benchmark can have, planted under the timed path
FAULTS = ("unchanged", "half_batch", "altered")


class FaultyBackend:
    """Delegating backend with one planted fault.

    * ``unchanged`` — a chunk returns the result it returned the previous
      time for the same worker, shard and rows: the state the step should
      have moved stays as it was;
    * ``half_batch`` — the second half of a multi-RHS block's columns is
      left out (returned as zeros);
    * ``altered`` — one answer is altered where it is produced: the first
      element of every chunk result is shifted by its max-norm.
    """

    def __init__(self, inner, fault: str):
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
        self.inner = inner
        self.fault = fault
        self._lock = threading.Lock()
        self._last: Dict[tuple, np.ndarray] = {}   # guarded_by: _lock

    def compute_chunk(self, worker_id: int, shard_id: str, shard: np.ndarray,
                      r0: int, r1: int, x: np.ndarray) -> np.ndarray:
        y = np.array(self.inner.compute_chunk(worker_id, shard_id, shard,
                                              r0, r1, x))
        if self.fault == "unchanged":
            key = (worker_id, shard_id, r0, x.shape)
            with self._lock:
                prev: Optional[np.ndarray] = self._last.get(key)
                self._last[key] = y
            return y if prev is None else prev
        if self.fault == "half_batch":
            if y.ndim == 2:
                y[:, y.shape[1] - y.shape[1] // 2:] = 0.0
            return y
        y.reshape(-1)[0] += np.max(np.abs(y)) + 1.0
        return y

    def drop_shard(self, worker_id: int, shard_id: str) -> None:
        self.inner.drop_shard(worker_id, shard_id)
