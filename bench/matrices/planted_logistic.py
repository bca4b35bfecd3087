"""``planted_logistic``: dense binary classification data with a planted
separator, Gisette's recipe scaled by rows; its reference is full-batch
gradient descent of L2-regularised logistic regression in float64 NumPy.

Features are standard normal (the row blocks of ``dense_gaussian``, so the
same seed gives the same matrix), the separator is ``w_true ~ N(0, 1/d)``
and the labels are ``sign(A·w_true + 0.5·noise)``.  Rows are drawn
independently, not copied from a smaller set, so no two coded blocks
coincide and a decode that mixes up rows cannot hide.  ``Problem.meta``
holds the labels ``y``.
"""

from __future__ import annotations

import pathlib

import numpy as np

from bench import data as bdata
from bench.manifest import find_module

#: seed stream of the separator and the label noise
STREAM_LABELS = 3
LABEL_NOISE = 0.5

_ROOT = pathlib.Path(__file__).resolve().parents[2]


def logistic_gd(a: np.ndarray, y: np.ndarray, w0: np.ndarray,
                l2: np.ndarray, lr: float, iters: int) -> np.ndarray:
    """``w_iters − w0`` of ``iters`` full-batch steps ``w ← w − lr·(Aᵀr/m +
    l2·w)``, ``r = −y ⊙ σ(−y ⊙ Aw)``, for each column of the ``(d, s)``
    block ``w0`` with its own penalty ``l2[j]``; float64 throughout."""
    m = a.shape[0]
    w = np.array(w0, dtype=np.float64)
    yc = y[:, None]
    for _ in range(iters):
        margin = yc * (a @ w)
        r = -yc / (1.0 + np.exp(np.minimum(margin, 700.0)))
        w = w - lr * (a.T @ r / m + l2[None, :] * w)
    return w - w0


def build(config, seed: int) -> bdata.Problem:
    rows, cols = int(config["rows"]), int(config["cols"])
    a = find_module("matrices", "dense_gaussian", _ROOT).generate(
        rows, cols, seed)
    rng = bdata.rng(seed, STREAM_LABELS)
    w_true = rng.standard_normal(cols) / np.sqrt(cols)
    logits = a @ w_true + LABEL_NOISE * rng.standard_normal(rows)
    y = np.where(logits > 0, 1.0, -1.0)

    def reference(w0: np.ndarray, l2: np.ndarray, lr: float,
                  iters: int) -> np.ndarray:
        return logistic_gd(a, y, w0, l2, lr, iters)

    return bdata.Problem(a, reference, {"rows": rows, "cols": cols, "y": y})
