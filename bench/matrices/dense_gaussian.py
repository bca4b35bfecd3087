"""``dense_gaussian``: a standard-normal float64 ``rows × cols`` matrix
from the seed; its reference is the float64 NumPy product ``A @ X``."""

from __future__ import annotations

import concurrent.futures

import numpy as np

from bench import data as bdata

#: row blocks of the generated matrix; fixed, so the values depend on the
#: seed alone and not on how many threads fill them
GEN_BLOCKS = 16


def generate(rows: int, cols: int, seed: int, threads: int = 8) -> np.ndarray:
    """Standard-normal float64 matrix, filled by row blocks in parallel."""
    out = np.empty((rows, cols), dtype=np.float64)
    bounds = np.linspace(0, rows, GEN_BLOCKS + 1).astype(int)
    children = bdata.seed_sequence(seed, bdata.STREAM_MATRIX).spawn(GEN_BLOCKS)

    def fill(i: int) -> None:
        np.random.default_rng(children[i]).standard_normal(
            out=out[bounds[i]:bounds[i + 1]])

    with concurrent.futures.ThreadPoolExecutor(threads) as ex:
        list(ex.map(fill, range(GEN_BLOCKS)))
    return out


def build(config, seed: int) -> bdata.Problem:
    a = generate(int(config["rows"]), int(config["cols"]), seed)

    def reference(xs: np.ndarray) -> np.ndarray:
        """``(rows, m)`` float64 products of the ``(cols, m)`` block."""
        return a @ xs

    return bdata.Problem(a, reference, {"rows": a.shape[0], "cols": a.shape[1]})
