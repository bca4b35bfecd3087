"""``logreg_gd``: each job ``iters`` full-batch gradient-descent steps of
L2-regularised logistic regression, one ``RegressionJob`` on the shared
matrix: ``A·w`` and ``Aᵀ·r`` are each a coded round, so a job is ``2 ·
iters`` dependent products.

A client's ``j``-th job starts from the next ``w0`` of its stride of a
pool drawn from the seed (``N(0, 1/d)``, a stand-in for a checkpoint);
client ``c`` uses the penalty ``l2_base · 2^c`` (a regularisation sweep)
and every client the step ``lr``.  After the window a sample of the jobs
done, drawn from the seed, is compared with the float64 reference
computed for all of them at once as a block of columns
(:meth:`Jobs.check`).  What is compared is ``w_iters − w0`` and not
``w_iters``, so that the exact ``w0`` does not dilute the error of the
products.
"""

from __future__ import annotations

import inspect
from typing import Any, Dict, List

import numpy as np

from bench import data as bdata


class Jobs:
    def __init__(self, cell, problem: bdata.Problem, data, strategy,
                 seed: int):
        from repro.cluster import RegressionJob
        if not {"w0", "l2"} <= set(inspect.signature(RegressionJob).parameters):
            raise RuntimeError("this program's RegressionJob takes no w0 and "
                               "l2: it cannot run a gradient-descent cell")
        tr = cell.traffic
        self.clients = int(tr["clients"])
        self.width = 1
        self.iters = int(tr["iters"])
        self.products = 2 * self.iters
        self.lr = float(tr["lr"])
        self.l2 = [float(tr["l2_base"]) * 2.0 ** c for c in range(self.clients)]
        self.sample = int(tr["check_sample"])
        self.problem = problem
        self.data = data
        self.strategy = strategy
        cols = problem.matrix.shape[1]
        self.pool = bdata.rng(seed, bdata.STREAM_OPERANDS).standard_normal(
            (int(tr["w0_pool"]), cols)) / np.sqrt(cols)

    def make(self, client: int, j: int):
        """(job, [pool index of its w0]) of client ``client``'s ``j``-th
        job."""
        from repro.cluster import RegressionJob
        n = self.pool.shape[0]
        i = (client * (n // self.clients) + j) % n
        return RegressionJob(self.problem.matrix, self.problem.meta["y"],
                             self.strategy, epochs=self.iters,
                             loss="logistic", lr=self.lr,
                             chunks=self.data.chunks, data=self.data,
                             w0=self.pool[i], l2=self.l2[client]), [i]

    def check(self, done: List[Any], seed: int) -> Dict[str, Any]:
        """The worst max-norm error of ``w_iters − w0`` over the sampled
        jobs, and the median over them of each job's median error.

        ``Aᵀ·r`` sums over every row, so where a job's ``A·w`` round
        decoded a chunk from parity workers, the decode's amplification of
        the float32 rounding reaches every entry of its gradient: that
        job's own median entry reads several times the others' (up to 7×
        on one v5e).  The median job reads the chunk product's precision,
        whatever the coverage, as the median entry does in ``matvec``.
        """
        pick = bdata.rng(seed, bdata.STREAM_SAMPLE).choice(
            len(done), size=min(self.sample, len(done)), replace=False)
        chosen = [done[i] for i in sorted(pick)]
        if not chosen:
            return {"checked": 0, "max_rel_err": None, "median_rel_err": None}
        w0 = self.pool[[r.operands[0] for r in chosen]].T          # (d, s)
        l2 = np.array([self.l2[r.client] for r in chosen])
        want = self.problem.reference(w0, l2, self.lr, self.iters)
        pairs = [(np.asarray(r.handle.output) - w0[:, j], want[:, j])
                 for j, r in enumerate(chosen)]
        return {"checked": len(chosen),
                "max_rel_err": max(bdata.max_rel_err(g, w) for g, w in pairs),
                "median_rel_err": float(np.median(
                    [bdata.median_rel_err(g, w) for g, w in pairs]))}
