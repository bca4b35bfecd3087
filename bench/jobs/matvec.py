"""``matvec``: each job one ``MatvecJob`` of ``batch`` vectors in one round.

A client's ``j``-th job takes the next ``batch`` vectors of its stride of
a pool drawn from the seed.  After the window, a sample of the jobs done,
drawn from the seed, is compared column by column with the float64
reference of the configuration's matrix.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from bench import data as bdata


class Jobs:
    def __init__(self, cell, problem: bdata.Problem, data, strategy,
                 seed: int):
        tr = cell.traffic
        self.clients = int(tr["clients"])
        self.width = int(tr["batch"])
        self.products = self.width
        self.sample = int(tr["check_sample"])
        self.problem = problem
        self.data = data
        self.strategy = strategy
        self.pool = bdata.rng(seed, bdata.STREAM_OPERANDS).standard_normal(
            (int(tr["vector_pool"]), problem.matrix.shape[1]))

    def make(self, client: int, j: int):
        """(job, operand indices) of client ``client``'s ``j``-th job."""
        from repro.cluster import MatvecJob
        n = self.pool.shape[0]
        first = client * (n // self.clients) + j * self.width
        idx = [(first + i) % n for i in range(self.width)]
        return MatvecJob(self.problem.matrix, [self.pool[i] for i in idx],
                         self.strategy, chunks=self.data.chunks,
                         batch=self.width, data=self.data), idx

    def check(self, done: List[Any], seed: int) -> Dict[str, Any]:
        """The worst max-norm and median error over the sampled answers
        (each column of a block is an answer)."""
        pick = bdata.rng(seed, bdata.STREAM_SAMPLE).choice(
            len(done), size=min(self.sample, len(done)), replace=False)
        chosen = [done[i] for i in sorted(pick)]
        idx = sorted({i for r in chosen for i in r.operands})
        if not idx:
            return {"checked": 0, "max_rel_err": None, "median_rel_err": None}
        ref = self.problem.reference(self.pool[idx].T)       # (rows, m)
        col = {i: c for c, i in enumerate(idx)}
        pairs = []
        for r in chosen:
            out = np.asarray(r.handle.output)                 # (B, rows)
            pairs += [(out[b], ref[:, col[i]])
                      for b, i in enumerate(r.operands)]
        return {"checked": len(chosen),
                "max_rel_err": max(bdata.max_rel_err(g, w) for g, w in pairs),
                "median_rel_err": max(bdata.median_rel_err(g, w)
                                      for g, w in pairs)}
