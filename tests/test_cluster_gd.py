"""Coded full-batch gradient descent: both products as coded rounds.

A :class:`RegressionJob` iteration is a round of ``A·w`` on the job's
matrix and a round of ``Aᵀ·r`` on its transpose, which the engine encodes
with the matrix's layout and installs once per matrix
(``load_transpose``).  Checked here against a plain float64 reference,
with the NumPy backend and with ``KernelBackend`` in interpret mode, on
shared and private matrices; the transpose's one install and its unload;
the width-aware work unit of planning and measured speeds; and the
journal spec of a job.
"""

import time

import numpy as np
import pytest

from repro.cluster import (ClusterConfig, CodedExecutionEngine, JobService,
                           NoSlowdown, RegressionJob, kernel_backend)
from repro.cluster.data import CodedData
from repro.cluster.service import _job_from_spec, _job_spec
from repro.core.strategies import GeneralS2C2, UncodedReplication

N, K = 12, 10
M, D = 2400, 200
LR, L2, EPOCHS = 0.5, 1e-2, 3


def problem(seed=0, rows=M, cols=D):
    """Gisette's recipe at a small size: normal features, a planted
    separator, labels sign(A·w_true + 0.5·noise), and a start point."""
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((rows, cols))
    w_true = rng.standard_normal(cols) / np.sqrt(cols)
    y = np.where(a @ w_true + 0.5 * rng.standard_normal(rows) > 0, 1.0, -1.0)
    w0 = rng.standard_normal(cols) / np.sqrt(cols)
    return a, y, w0


def reference(a, y, w0, lr, l2, epochs, loss="logistic"):
    """Plain float64 full-batch gradient descent, one step at a time."""
    m = a.shape[0]
    w = w0.copy()
    for _ in range(epochs):
        margin = y * (a @ w)
        if loss == "logistic":
            grad = a.T @ (-y / (1.0 + np.exp(margin))) / m
        else:
            grad = (a.T @ (-y * (margin < 1)) + 1e-3 * w) / m
        w = w - lr * (grad + l2 * w)
    return w


def engine(compute=None, row_cost=1e-6, **kw):
    cfg = ClusterConfig(n_workers=N, k=K, row_cost=row_cost, **kw)
    if compute is None:
        return CodedExecutionEngine(cfg, NoSlowdown())
    return CodedExecutionEngine(cfg, NoSlowdown(), compute=compute)


def _shards(eng):
    return [set(w.shards) for w in eng.workers]


@pytest.mark.parametrize("backend,chunks", [("numpy", 20), ("kernel", 4)])
@pytest.mark.parametrize("shared", [True, False])
def test_gd_job_matches_the_reference(backend, chunks, shared):
    a, y, w0 = problem()
    eng = engine(kernel_backend() if backend == "kernel" else None)
    svc = JobService(eng)
    try:
        data = svc.share_matrix(a, chunks=chunks) if shared else None
        job = RegressionJob(a, y, GeneralS2C2(N, K, M, chunks=chunks),
                            epochs=EPOCHS, lr=LR, chunks=chunks, data=data,
                            w0=w0, l2=L2)
        h = svc.submit(job)
        svc.drain(timeout=300)
        assert h.metrics.error is None, h.metrics.error
        want = reference(a, y, w0, LR, L2, EPOCHS)
        # float32 chunk products, amplified up to ~1e3x in the chunks that
        # parity workers covered (the decode of the Cauchy code)
        tol = 1e-12 if backend == "numpy" else 1e-4
        np.testing.assert_allclose(h.output - w0, want - w0, rtol=0.0,
                                   atol=tol * np.max(np.abs(want - w0)))
        rounds = h.metrics.rounds
        assert [r.cols for r in rounds] == [D, M] * EPOCHS
        assert [r.rows_per_chunk for r in rounds] == \
            [M // (K * chunks), D // (K * chunks)] * EPOCHS
        assert h.metrics.gd_steps == EPOCHS and h.metrics.gd_step_s > 0.0
        assert eng.registry.value("s2c2_transpose_installs_total") == 1
        # a private job's matrix and transpose go at its end; shared ones
        # stay until the service closes
        assert all(len(s) == (2 if shared else 0) for s in _shards(eng))
    finally:
        svc.close()
        eng.shutdown()
    assert all(not s for s in _shards(eng))


@pytest.mark.parametrize("loss,strategy", [
    ("hinge", GeneralS2C2(N, K, M, chunks=20)),
    ("logistic", UncodedReplication(N, M))])
def test_hinge_loss_and_replicated_layout(loss, strategy):
    """The hinge loss, and a replicated matrix, whose transpose takes its
    replica placement, run the same two rounds an iteration."""
    a, y, w0 = problem(1)
    eng = engine()
    svc = JobService(eng)
    try:
        h = svc.submit(RegressionJob(a, y, strategy, epochs=EPOCHS, loss=loss,
                                     lr=LR, chunks=20, w0=w0, l2=L2))
        svc.drain(timeout=120)
        assert h.metrics.error is None, h.metrics.error
        np.testing.assert_allclose(
            h.output, reference(a, y, w0, LR, L2, EPOCHS, loss),
            rtol=1e-10, atol=1e-12)
        assert len(h.metrics.rounds) == 2 * EPOCHS
    finally:
        svc.close()
        eng.shutdown()


def test_defaults_start_at_zero_without_penalty():
    a, y, _ = problem(2)
    eng = engine()
    svc = JobService(eng)
    try:
        h = svc.submit(RegressionJob(a, y, GeneralS2C2(N, K, M, chunks=20),
                                     epochs=2, lr=LR, chunks=20))
        svc.drain(timeout=120)
        np.testing.assert_allclose(
            h.output, reference(a, y, np.zeros(D), LR, 0.0, 2),
            rtol=1e-10, atol=1e-13)
    finally:
        svc.close()
        eng.shutdown()
    with pytest.raises(ValueError, match="w0"):
        RegressionJob(a, y, GeneralS2C2(N, K, M, chunks=20), w0=np.zeros(3))


def test_concurrent_first_jobs_install_the_transpose_once():
    a, y, w0 = problem(3)
    eng = engine()
    encoded = []
    real_encode = CodedData.encode

    def counting_encode(shard_id, m, code, chunks):
        encoded.append(m.shape)
        time.sleep(0.05)            # hold the install open for the others
        return real_encode(shard_id, m, code, chunks)

    svc = JobService(eng, max_inflight=4)
    try:
        data = svc.share_matrix(a, chunks=20)
        CodedData.encode = staticmethod(counting_encode)
        try:
            hs = [svc.submit(RegressionJob(
                a, y, GeneralS2C2(N, K, M, chunks=20), epochs=1, lr=LR,
                chunks=20, data=data, w0=w0, l2=L2)) for _ in range(4)]
            svc.drain(timeout=120)
        finally:
            CodedData.encode = staticmethod(real_encode)
        assert all(h.metrics.error is None for h in hs)
        assert encoded == [(D, M)]
        assert eng.registry.value("s2c2_transpose_installs_total") == 1
        want = reference(a, y, w0, LR, L2, 1)
        for h in hs:
            np.testing.assert_allclose(h.output, want, rtol=1e-10,
                                       atol=1e-13)
        assert all(len(s) == 2 for s in _shards(eng))
    finally:
        svc.close()
        eng.shutdown()
    assert all(not s for s in _shards(eng))


def test_transpose_of_another_matrix_is_refused():
    a, _, _ = problem(4)
    eng = engine()
    try:
        data = eng.load_matrix(a, chunks=20)
        with pytest.raises(ValueError, match="load_transpose"):
            eng.load_transpose(data, a[:100])
    finally:
        eng.shutdown()


class TestWidthAwareUnit:
    """A chunk's work counts its elements: with one width nothing changes,
    and a speed learned on one matrix plans a matrix of another width."""

    def test_one_width_keeps_row_cost(self):
        eng = engine(row_cost=3e-5)
        try:
            data = eng.load_matrix(np.ones((480, 24)), chunks=4)
            assert eng._row_cost(data) == 3e-5
        finally:
            eng.shutdown()

    def test_equal_elements_per_chunk_plan_equal_makespans(self):
        a, _, _ = problem(5)                 # 2400 x 200: chunks of 12 rows
        b = np.random.default_rng(6).standard_normal((1200, 400))  # 6 rows
        eng = engine(row_cost=1e-5)
        try:
            da = eng.load_matrix(a, chunks=20)
            db = eng.load_matrix(b, chunks=20)
            sa = GeneralS2C2(N, K, M, chunks=20)
            for _ in range(3):
                eng.matvec(da, np.ones(D), sa)
            sb = GeneralS2C2(N, K, 1200, chunks=20)
            _, plan_a = eng._plan(da, sa, 1, eng._row_cost(da))
            _, plan_b = eng._plan(db, sb, 1, eng._row_cost(db))
            assert plan_b == pytest.approx(plan_a, rel=1e-12)
            assert eng._row_cost(db) == pytest.approx(2e-5)
            out = eng.matvec(db, np.ones(400), sb)
            np.testing.assert_allclose(out.y, b @ np.ones(400), rtol=1e-9,
                                       atol=1e-9)
        finally:
            eng.shutdown()

    def test_speed_measured_on_one_width_holds_on_another(self):
        """Chunks whose compute time is proportional to their elements:
        the speeds measured on a matrix and on its transpose agree (a
        per-row unit would read the wide one half as fast)."""

        class ElementTimed:
            def compute_chunk(self, worker_id, shard_id, shard, r0, r1, x):
                time.sleep((r1 - r0) * shard.shape[1] * 2e-6)
                return shard[r0:r1] @ x

        a = np.random.default_rng(7).standard_normal((1200, 240))
        eng = engine(ElementTimed(), row_cost=1e-12, enable_stealing=False)
        try:
            da = eng.load_matrix(a, chunks=10)       # 12 rows of 240
            dt = eng.load_transpose(da, a)           # 2.4 rows → 3 of 1200
            sa = GeneralS2C2(N, K, 1200, chunks=10)
            st = GeneralS2C2(N, K, 240, chunks=10)
            fwd = [eng.matvec(da, np.ones(240), sa).metrics for _ in range(3)]
            bwd = [eng.matvec(dt, np.ones(1200), st).metrics
                   for _ in range(3)]

            def speed(rounds):
                s = np.concatenate([r.speeds_measured for r in rounds])
                return float(np.median(s[s > 0]))

            # 3 rows of 1200 against 12 of 240, 1.25x the elements a chunk:
            # a per-row unit reads the ratio 3 / 1.25 / 12 = 0.2
            ratio = speed(bwd) / speed(fwd)
            assert 0.5 < ratio < 2.0, ratio
        finally:
            eng.shutdown()


def test_journal_spec_round_trips_w0_and_l2():
    a, y, w0 = problem(8, rows=240, cols=12)
    job = RegressionJob(a, y, GeneralS2C2(N, K, 240, chunks=2), epochs=4,
                        loss="hinge", lr=0.25, chunks=2, w0=w0, l2=0.125)
    back = _job_from_spec({"job": _job_spec(job)})
    assert isinstance(back, RegressionJob)
    np.testing.assert_array_equal(back.w0, w0)
    assert back.l2 == 0.125
    assert (back.epochs, back.loss, back.lr, back.chunks) == (4, "hinge",
                                                             0.25, 2)
    np.testing.assert_array_equal(back.a, a)
    np.testing.assert_array_equal(back.y, y)


def test_shared_transpose_rounds_coalesce():
    """With coalescing on, concurrent jobs' Aᵀ rounds on a shared matrix
    merge like its A rounds do."""
    a, y, w0 = problem(9)
    eng = engine()
    svc = JobService(eng, max_inflight=4, coalesce=True,
                     coalesce_hold_s=0.05)
    try:
        data = svc.share_matrix(a, chunks=20)
        hs = []
        for i in range(4):
            hs.append(svc.submit(RegressionJob(
                a, y, GeneralS2C2(N, K, M, chunks=20), epochs=1, lr=LR,
                chunks=20, data=data, w0=w0 * (i + 1), l2=L2)))
        svc.drain(timeout=120)
        for i, h in enumerate(hs):
            assert h.metrics.error is None
            np.testing.assert_allclose(
                h.output, reference(a, y, w0 * (i + 1), LR, L2, 1),
                rtol=1e-10, atol=1e-13)
        merged = [r for h in hs for r in h.metrics.rounds if r.coalesced > 1]
        assert any(r.cols == M for r in merged)
    finally:
        svc.close()
        eng.shutdown()
