"""Straggler emulation relative to what the program achieves on the chip.

A worker of the program stretches each chunk to ``rows · B · row_cost / s``
seconds for the speed ``s`` its injector returns, sleeping whatever the
compute left over.  A fixed ``row_cost`` makes that sleep either the whole
chunk (no device change can show) or nothing (stragglers vanish), depending
on how fast the program is, and a chunk time taken from a warm-up moves
from run to run and sets the pace of the whole window.  So the benchmark
never lets the worker sleep (:class:`MixInjector` hands it
:data:`NO_SLEEP`) and stretches each chunk itself, by that chunk's own
compute time: a worker at mix speed ``s`` (1 = the fastest node) sleeps
``compute · (1/s − 1)`` after each chunk call
(``bench.backends.TimedBackend``), so its chunk lasts ``compute / s``.
The speed-1 worker sleeps nothing.  A faster program shortens every
chunk, the stragglers' too, as a cluster of faster nodes would.

The mix itself is read from ``bench/mixes/<name>.json``:

* ``controlled`` — the paper's controlled cluster (§7.1):
  :func:`controlled_traces`, a copy of ``repro.core.traces``'s generator,
  with the trace seed fixed in the mix so that every run sees the same
  speeds;
* ``balanced`` — every worker at speed 1 and nothing slept.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional

import numpy as np

#: speed handed to the program's worker, so that it never sleeps (its
#: target time is then far below any real chunk time)
NO_SLEEP = 1e9


def controlled_traces(n_nodes: int, n_iters: int, n_stragglers: int,
                      nonstraggler_variation: float = 0.2,
                      straggler_slowdown: float = 5.0,
                      drift_sigma: float = 0.01,
                      seed: int = 0) -> np.ndarray:
    """Controlled-cluster scenario: exactly ``n_stragglers`` persistent
    stragglers (the last nodes), non-stragglers spread uniformly over
    [1 - variation, 1] with small drift — the paper's §7.1 setup."""
    rng = np.random.default_rng(seed)
    base = rng.uniform(1.0 - nonstraggler_variation, 1.0, size=n_nodes)
    # fastest non-straggler pinned to 1.0 so the 5x slowdown is relative to it
    base[np.argmax(base[: n_nodes - n_stragglers] if n_stragglers else base)] = 1.0
    if n_stragglers:
        base[-n_stragglers:] = 1.0 / straggler_slowdown
    drift = drift_sigma * rng.standard_normal((n_iters, n_nodes))
    out = base[None, :] * np.exp(np.cumsum(drift, axis=0) * 0.1)
    return np.maximum(out, 0.01)


def mix_speeds(mix: Dict[str, Any], n_workers: int) -> Optional[np.ndarray]:
    """The ``(T, n)`` speed trace of a mix, or ``None`` for ``balanced``."""
    kind = mix["kind"]
    if kind == "balanced":
        return None
    if kind == "controlled":
        return controlled_traces(
            n_workers, int(mix["iters"]), int(mix["n_stragglers"]),
            nonstraggler_variation=float(mix["variation"]),
            straggler_slowdown=float(mix["slowdown"]),
            drift_sigma=float(mix["drift_sigma"]),
            seed=int(mix["trace_seed"]))
    raise ValueError(f"unknown straggler mix kind {kind!r}")


class MixInjector:
    """The program's slowdown injector, and the benchmark's stretch.

    The worker asks :meth:`speed` right before each chunk call, on its own
    thread; the answer is always :data:`NO_SLEEP`, and the mix's speed at
    that chunk's iteration is kept for :meth:`slowdown`, which the
    benchmark's backend asks on the same thread right after the call.
    Until :attr:`active` is set (the warm-up that compiles) nobody sleeps.
    """

    def __init__(self, speeds: Optional[np.ndarray]):
        self.speeds = speeds
        self.active = False
        self._lock = threading.Lock()
        self._now: Dict[int, float] = {}   # guarded_by: _lock

    def mix_speed(self, worker: int, iteration: int) -> float:
        """The mix's own speed (1 = fastest node) of ``worker``."""
        if self.speeds is None:
            return 1.0
        it = min(int(iteration), self.speeds.shape[0] - 1)
        return float(self.speeds[it, worker])

    def speed(self, worker: int, iteration: int) -> float:
        s = self.mix_speed(worker, iteration)
        with self._lock:
            self._now[worker] = s
        return NO_SLEEP

    def slowdown(self, worker: int) -> float:
        """``1/s − 1`` for the worker's current chunk: the seconds it sleeps
        per second of compute."""
        if not self.active or self.speeds is None:
            return 0.0
        with self._lock:
            s = self._now.get(worker, 1.0)
        return 1.0 / s - 1.0
