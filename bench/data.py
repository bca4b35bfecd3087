"""What every configuration's data shares: seed streams, the problem, and
the error measures the check compares.

A configuration's matrix is made by ``bench/matrices/<matrix>.py``, whose
``build(config, seed)`` returns a :class:`Problem`: the matrix handed to
the program, and a plain reference that imports nothing of the program
and takes nothing it made.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import numpy as np

# stream ids: one independent stream per use of the seed
STREAM_MATRIX, STREAM_OPERANDS, STREAM_SAMPLE = 0, 1, 2


def seed_sequence(seed: int, stream: int) -> np.random.SeedSequence:
    """A stream of the run's seed; any whole number, negative ones too."""
    return np.random.SeedSequence([abs(int(seed)), int(seed < 0), stream])


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_sequence(seed, stream))


class Problem:
    """The matrix a cell shares, and its reference."""

    def __init__(self, matrix: np.ndarray, reference: Callable[..., np.ndarray],
                 meta: Dict[str, Any]):
        self.matrix = matrix
        self.reference = reference
        self.meta = meta


def max_rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Max-norm error over the max-norm of the float64 reference."""
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def median_rel_err(got: np.ndarray, want: np.ndarray,
                   mask: Optional[np.ndarray] = None) -> float:
    """Median entry error over the RMS of the float64 reference.

    The decode amplifies the chunk products' rounding only in the rows it
    reconstructs from parity workers, a share that changes with which
    workers happened to answer first; the median entry is a systematic
    one in every coverage pattern, so it reads the precision of the chunk
    product itself, steadily from run to run.  ``mask`` keeps the entries
    whose product is not zero by construction, so that the median is not
    an exact zero by chance.
    """
    err = np.abs(got - want)
    if mask is not None:
        err = err[mask]
    return float(np.median(err) / np.sqrt(np.mean(want * want)))
