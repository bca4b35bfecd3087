"""Multi-tenant JobService: concurrent heterogeneous jobs over one engine.

Acceptance: the service sustains ≥ 100 queued heterogeneous jobs in one
run and reports per-strategy throughput, p50/p99 latency, and wasted-work
fraction; plus bounded-queue backpressure and per-job fault isolation.
"""

import threading

import numpy as np
import pytest

from repro.cluster import (ClusterConfig, CodedExecutionEngine, JobService,
                           MatvecJob, NoSlowdown, PageRankJob, RegressionJob,
                           ServiceSaturated, TraceInjector)
from repro.core.strategies import (BasicS2C2, GeneralS2C2, MDSCoded,
                                   UncodedReplication)
from repro.core.traces import controlled_traces

RNG = np.random.default_rng(7)

N, K, C, D = 6, 4, 8, 192


def make_service(row_cost=1e-6, max_queue=256, injector=None):
    eng = CodedExecutionEngine(
        ClusterConfig(n_workers=N, k=K, row_cost=row_cost),
        injector=injector or NoSlowdown())
    return eng, JobService(eng, max_queue=max_queue)


def make_stochastic_matrix(n, seed):
    rng = np.random.default_rng(seed)
    adj = (rng.random((n, n)) < 0.1).astype(np.float64)
    col = adj.sum(0, keepdims=True)
    m = adj / np.maximum(col, 1)
    m[:, col[0] == 0] = 1.0 / n
    return m


def make_job(i: int):
    """Heterogeneous mix cycling kinds × strategies."""
    strat = [GeneralS2C2(N, K, D, chunks=C),
             BasicS2C2(N, K, D, chunks=C),
             MDSCoded(N, K, D),
             UncodedReplication(N, D)][i % 4]
    kind = i % 3
    if kind == 0:
        a = RNG.standard_normal((D, 24))
        xs = [RNG.standard_normal(24) for _ in range(3)]
        return MatvecJob(a, xs, strat, chunks=C), ("matvec", a, xs)
    if kind == 1:
        m = make_stochastic_matrix(D, seed=i)
        return PageRankJob(m, strat, iters=3, chunks=C), ("pagerank", m, None)
    a = RNG.standard_normal((D, 12))
    y = np.sign(a @ RNG.standard_normal(12) + 0.1 * RNG.standard_normal(D))
    return RegressionJob(a, y, strat, epochs=3, chunks=C), ("regression", a, y)


class TestServiceThroughput:
    def test_sustains_100_plus_heterogeneous_jobs(self):
        """≥100 queued jobs, 4 concurrent producers, full report at the end."""
        eng, svc = make_service()
        n_jobs = 120
        handles = [None] * n_jobs
        refs = [None] * n_jobs
        errors = []

        def producer(lo, hi):
            for i in range(lo, hi):
                job, ref = make_job(i)
                refs[i] = ref
                try:
                    handles[i] = svc.submit(job)
                except ServiceSaturated as exc:   # pragma: no cover
                    errors.append(exc)

        try:
            threads = [threading.Thread(target=producer,
                                        args=(j * 30, (j + 1) * 30))
                       for j in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errors
            svc.drain(timeout=300)

            rep = svc.report()
            assert rep.n_jobs == n_jobs
            # 3 rounds a matvec or PageRank job; a regression job's 3
            # epochs are 6 (A·w and Aᵀ·r each)
            n_regression = sum(1 for i in range(n_jobs) if i % 3 == 2)
            assert rep.n_rounds == (n_jobs - n_regression) * 3 \
                + n_regression * 6
            assert rep.jobs_per_s > 0
            assert np.isfinite(rep.p50_latency) and np.isfinite(rep.p99_latency)
            assert rep.p99_latency >= rep.p50_latency > 0
            assert 0.0 <= rep.wasted_fraction < 1.0
            # per-strategy breakdown covers all four strategies
            assert set(rep.by_strategy) == {"GeneralS2C2", "BasicS2C2",
                                            "MDSCoded", "UncodedReplication"}
            for s in rep.by_strategy.values():
                assert s["jobs"] == n_jobs / 4
                assert s["p99_latency"] >= s["p50_latency"] > 0
                assert 0.0 <= s["wasted_fraction"] < 1.0
            # no job errored
            assert all(m.error is None for m in svc.completed)

            # spot-check numerical results against references
            for i in (0, 5, 13, 42, 99):
                kind, a, extra = refs[i]
                out = handles[i].output
                if kind == "matvec":
                    want = np.stack([a @ x for x in extra])
                    np.testing.assert_allclose(out, want, rtol=1e-9, atol=1e-9)
                elif kind == "pagerank":
                    r = np.ones(D) / D
                    for _ in range(3):
                        r = 0.15 / D + 0.85 * (a @ r)
                    np.testing.assert_allclose(out, r, rtol=1e-9, atol=1e-9)
        finally:
            svc.close()
            eng.shutdown()

    def test_regression_job_learns(self):
        eng, svc = make_service()
        try:
            a = RNG.standard_normal((D, 12))
            w_true = RNG.standard_normal(12)
            y = np.sign(a @ w_true)
            job = RegressionJob(a, y, GeneralS2C2(N, K, D, chunks=C),
                                epochs=30, loss="logistic", lr=2.0, chunks=C)
            h = svc.submit(job)
            svc.drain(timeout=120)
            acc = ((a @ h.output > 0) * 2 - 1 == y).mean()
            assert acc > 0.9
        finally:
            svc.close()
            eng.shutdown()


class TestBackpressure:
    def test_bounded_queue_saturates(self):
        """Admission control: when the queue is full, submit raises instead
        of buffering unboundedly."""
        # slow rounds so the queue genuinely backs up
        traces = controlled_traces(N, 4, n_stragglers=1, seed=0)
        eng, svc = make_service(row_cost=2e-4, max_queue=2,
                                injector=TraceInjector(traces))
        try:
            a = RNG.standard_normal((D, 16))
            xs = [RNG.standard_normal(16) for _ in range(2)]
            saturated = 0
            for i in range(30):
                try:
                    svc.submit(MatvecJob(a, xs, GeneralS2C2(N, K, D, chunks=C),
                                         chunks=C))
                except ServiceSaturated:
                    saturated += 1
            assert saturated > 0
            svc.drain(timeout=120)
            rep = svc.report()
            assert rep.n_jobs == 30 - saturated
        finally:
            svc.close()
            eng.shutdown()

    def test_job_error_is_isolated(self):
        """A misconfigured job records an error; the service keeps serving."""
        eng, svc = make_service()
        try:
            a = RNG.standard_normal((D, 16))
            x = RNG.standard_normal(16)
            # strategy chunking disagrees with the data chunking -> ValueError
            bad = MatvecJob(a, [x], GeneralS2C2(N, K, D, chunks=C + 1),
                            chunks=C)
            good = MatvecJob(a, [x], GeneralS2C2(N, K, D, chunks=C), chunks=C)
            hb = svc.submit(bad)
            hg = svc.submit(good)
            svc.drain(timeout=120)
            assert hb.metrics.error is not None
            assert hg.metrics.error is None
            np.testing.assert_allclose(hg.output[0], a @ x, rtol=1e-9,
                                       atol=1e-9)
        finally:
            svc.close()
            eng.shutdown()
