"""Runs of contiguous chunks: one shard-aware backend call for several.

A :class:`~repro.cluster.worker.Worker` with a shard-aware backend hands
it the head of its queue together with the queued chunks of the same task
whose rows continue it — at most half, rounded up, of the task's queued
chunks, rounded down to the ladder 1, 2, 3, 4, 6, 8, … that
``Worker.warm_runs`` compiles ahead — and splits the call's
result back into one :class:`ChunkDone` per chunk.  A plain ``compute(a_rows, x)`` backend
keeps one call per chunk.
"""

import queue
import threading

import numpy as np
import pytest

from repro.cluster import (ClusterConfig, CodedExecutionEngine, NoSlowdown,
                           Worker, kernel_backend)
from repro.cluster import obs
from repro.cluster.worker import (ChunkDone, ChunkTask, WorkerDone,
                                  WorkerFailed, run_lengths)
from repro.core.strategies import GeneralS2C2

RNG = np.random.default_rng(15)


class _Recorder:
    """Shard-aware backend that records each call's rows (and can hold a
    call until released, or raise on a run of several chunks)."""

    def __init__(self, hold=False, raise_above=None):
        self.calls = []
        self.entered = threading.Event()
        self.release = threading.Event()
        if not hold:
            self.release.set()
        self.raise_above = raise_above

    def compute_chunk(self, worker_id, shard_id, shard, r0, r1, x):
        self.calls.append((r0, r1))
        self.entered.set()
        self.release.wait(60)
        if self.raise_above is not None and r1 - r0 > self.raise_above:
            raise RuntimeError("injected failure of a run")
        return shard[r0:r1] @ x


def _task(chunks, round_id=1, x=None, cols=4):
    return ChunkTask(round_id=round_id, iteration=0, shard_id="s",
                     chunks=list(chunks),
                     x=RNG.standard_normal(cols) if x is None else x,
                     row_cost=0.0, cancel=threading.Event())


def _even(n, rows, start=0):
    """``n`` chunks of ``rows`` rows each, ids and rows in order."""
    return [(c, (start + c) * rows, (start + c + 1) * rows)
            for c in range(n)]


def _collect(events, n_done, timeout=60):
    """Events until ``n_done`` chunk results have arrived (and any
    terminal event right behind them)."""
    out, done = [], 0
    while done < n_done:
        ev = events.get(timeout=timeout)
        out.append(ev)
        done += isinstance(ev, ChunkDone)
        if isinstance(ev, WorkerFailed):
            break
    return out


def _run(compute, tasks, shard_rows, cols=4, shard=None):
    """Queue ``tasks`` on a worker not yet started, so that its first pop
    sees the whole queue; run them to the end.  Returns (every event,
    shard)."""
    events = queue.Queue()
    w = Worker(0, events, NoSlowdown(), compute=compute)
    if shard is None:
        shard = RNG.standard_normal((shard_rows, cols))
    w.install_shard("s", shard)
    for t in tasks:
        w.submit(t)
    w.stop()                    # drains the queue, then ends the thread
    w.start()
    w.join(timeout=60)
    assert not w.is_alive()
    evs = []
    while not events.empty():
        evs.append(events.get_nowait())
    return evs, shard


class TestRunBoundaries:
    def test_one_call_for_a_contiguous_run(self):
        rec = _Recorder()
        task = _task(_even(6, 8))
        evs, shard = _run(rec, [task], 48)
        # runs of 3, 2 and 1: half the task's queued chunks each time
        assert rec.calls == [(0, 24), (24, 40), (40, 48)]
        done = [e for e in evs if isinstance(e, ChunkDone)]
        assert [e.chunk_id for e in done] == list(range(6))
        for e, (_c, r0, r1) in zip(done, task.chunks):
            np.testing.assert_array_equal(e.result, shard[r0:r1] @ task.x)
        assert isinstance(evs[-1], WorkerDone) and evs[-1].chunks_done == 6
        assert not evs[-1].cancelled

    @pytest.mark.parametrize("n,want", [
        (1, [1]), (2, [1, 1]), (3, [2, 1]), (8, [4, 2, 1, 1]),
        # longer runs are rounded down to the ladder 4, 6, 8, 12, …
        (11, [6, 3, 1, 1]), (15, [8, 4, 2, 1]), (17, [8, 4, 3, 1, 1])])
    def test_run_takes_at_most_half_of_the_queued_chunks(self, n, want):
        rec = _Recorder()
        _run(rec, [_task(_even(n, 8))], 8 * n)
        assert [(r1 - r0) // 8 for r0, r1 in rec.calls] == want

    def test_run_stops_at_a_task_boundary(self):
        """Two tasks whose rows follow on: no call spans both."""
        rec = _Recorder()
        a = _task(_even(1, 8))
        b = _task([(c, r0, r1) for c, r0, r1 in _even(3, 8, start=1)])
        _run(rec, [a, b], 32)
        assert rec.calls == [(0, 8), (8, 24), (24, 32)]

    def test_run_stops_where_the_allocation_wraps(self):
        """An S2C2 allocation that wraps from the last chunk to chunk 0:
        rows 24:32 are not followed by rows 0:8."""
        rec = _Recorder()
        task = _task([(3, 24, 32), (0, 0, 8), (1, 8, 16), (2, 16, 24)])
        evs, _ = _run(rec, [task], 32)
        assert rec.calls == [(24, 32), (0, 16), (16, 24)]
        assert [e.chunk_id for e in evs if isinstance(e, ChunkDone)] == \
            [3, 0, 1, 2]

    @pytest.mark.parametrize("rows,want", [
        (400, [3, 2, 1]),             # a first call of 1200 rows
        (512, [3, 2, 1]),
        (513, [3, 2, 1]),             # over half a kernel block: runs too
        (328, [8, 6, 3, 2, 1])])      # the benchmark's 20 chunks a shard
    def test_run_rows_are_bounded_by_the_half_rule_alone(self, rows, want):
        """However many rows a chunk holds, a run takes the half of the
        task's queue the ladder allows: calls of more than 1024 rows."""
        rec = _Recorder()
        n = sum(want)
        _run(rec, [_task(_even(n, rows), cols=2)], n * rows, cols=2)
        assert [(r1 - r0) // rows for r0, r1 in rec.calls] == want
        assert rec.calls[0][1] - rec.calls[0][0] > 1024

    def test_numpy_backend_keeps_one_call_per_chunk(self):
        seen = []

        def compute(a_rows, x):
            seen.append(len(a_rows))
            return a_rows @ x

        task = _task(_even(6, 8))
        evs, shard = _run(compute, [task], 48)
        assert seen == [8] * 6
        done = [e for e in evs if isinstance(e, ChunkDone)]
        for e, (_c, r0, r1) in zip(done, task.chunks):
            np.testing.assert_array_equal(e.result, shard[r0:r1] @ task.x)
        # a plain backend charges no call record
        assert all(e.cost == obs.NO_COST for e in done)


class TestKernelRuns:
    @pytest.mark.parametrize("width", [None, 3])
    def test_run_results_equal_per_chunk_calls(self, width):
        """Through KernelBackend (interpret mode on the CPU), each chunk's
        slice of a run's result is bit for bit the chunk's own call."""
        rows, n, cols = 8, 5, 128
        shard = RNG.standard_normal((rows * n, cols))
        x = RNG.standard_normal(cols if width is None else (cols, width))
        x.flags.writeable = False
        task = _task(_even(n, rows), x=x)
        be = kernel_backend()
        evs, _ = _run(be, [task], rows * n, shard=shard)
        done = [e for e in evs if isinstance(e, ChunkDone)]
        assert [e.chunk_id for e in done] == list(range(n))
        # runs of 3, 1 and 1: one call record each
        assert [obs.CallCost(*e.cost).calls for e in done] == [1, 0, 0, 1, 1]
        for e, (c, r0, r1) in zip(done, task.chunks):
            alone = be.compute_chunk(0, "s", shard, r0, r1, x)
            assert e.result.shape == alone.shape
            assert e.result.dtype == np.float64
            np.testing.assert_array_equal(e.result, alone)

    @pytest.mark.parametrize("rows", [328, 480])
    def test_a_run_of_more_than_1024_rows_is_one_call(self, rows):
        """Through KernelBackend, a run of 4 chunks of the benchmark's
        sizes is one call of 1312 or 1920 rows (blocks of 656 or 960), and
        each chunk's slice of it is bit for bit the chunk's own call."""
        n, cols = 8, 128
        shard = RNG.standard_normal((rows * n, cols))
        x = RNG.standard_normal(cols)
        x.flags.writeable = False
        task = _task(_even(n, rows), x=x)
        be = kernel_backend()
        calls = []
        compute_chunk = be.compute_chunk

        def spy(worker_id, shard_id, a, r0, r1, x_):
            calls.append((r0, r1))
            return compute_chunk(worker_id, shard_id, a, r0, r1, x_)

        be.compute_chunk = spy
        evs, _ = _run(be, [task], rows * n, shard=shard)
        assert [(r1 - r0) // rows for r0, r1 in calls] == [4, 2, 1, 1]
        done = [e for e in evs if isinstance(e, ChunkDone)]
        assert [e.chunk_id for e in done] == list(range(n))
        for e, (c, r0, r1) in zip(done, task.chunks):
            np.testing.assert_array_equal(
                e.result, compute_chunk(0, "s", shard, r0, r1, x))

    def test_tracer_gets_a_chunk_record_per_chunk_of_a_run(self):
        tr = obs.Tracer()
        events = queue.Queue()
        rec = _Recorder()
        w = Worker(0, events, NoSlowdown(), compute=rec, tracer=tr)
        w.install_shard("s", RNG.standard_normal((24, 4)))
        w.submit(_task(_even(3, 8)))
        w.start()
        try:
            _collect(events, 3)
        finally:
            w.stop()
            w.join(timeout=10)
        spans = [r for r in tr.snapshot() if r.kind == obs.KIND_CHUNK]
        assert [r.chunk_id for r in spans] == [0, 1, 2]
        assert spans[0].t == spans[1].t and spans[0].dur == spans[1].dur
        assert spans[2].t >= spans[1].t + spans[1].dur
        assert all(dict(r.args)["rows"] == 8 for r in spans)


class TestRunSemantics:
    def test_retract_during_a_run_takes_only_queued_chunks(self):
        rec = _Recorder(hold=True)
        events = queue.Queue()
        w = Worker(0, events, NoSlowdown(), compute=rec)
        shard = RNG.standard_normal((64, 4))
        w.install_shard("s", shard)
        task = _task(_even(8, 8))
        w.submit(task)
        w.start()
        try:
            assert rec.entered.wait(60)
            # the run of the first four is under way: it is not retractable
            taken = w.retract(1, list(range(8)))
            assert sorted(taken) == [4, 5, 6, 7]
            assert w.backlog() == 0
            rec.release.set()
            evs = _collect(events, 4)
            assert [e.chunk_id for e in evs if isinstance(e, ChunkDone)] \
                == [0, 1, 2, 3]
            end = events.get(timeout=60)
            assert isinstance(end, WorkerDone) and end.chunks_done == 4
        finally:
            rec.release.set()
            w.stop()
            w.join(timeout=10)
        assert rec.calls == [(0, 32)]
        assert events.empty()

    def test_cancel_is_checked_once_per_run(self):
        rec = _Recorder(hold=True)
        events = queue.Queue()
        w = Worker(0, events, NoSlowdown(), compute=rec)
        w.install_shard("s", RNG.standard_normal((48, 4)))
        task = _task(_even(6, 8))
        w.submit(task)
        w.start()
        try:
            assert rec.entered.wait(60)
            task.cancel.set()           # mid-run: the run still reports
            rec.release.set()
            evs = _collect(events, 3)
            ack = events.get(timeout=60)
        finally:
            rec.release.set()
            w.stop()
            w.join(timeout=10)
        assert [e.chunk_id for e in evs if isinstance(e, ChunkDone)] == \
            [0, 1, 2]
        assert isinstance(ack, WorkerDone) and ack.cancelled
        assert ack.chunks_done == 3
        assert rec.calls == [(0, 24)]

    def test_a_raising_run_is_one_worker_failed(self):
        rec = _Recorder(raise_above=8)
        events = queue.Queue()
        w = Worker(0, events, NoSlowdown(), compute=rec)
        w.install_shard("s", RNG.standard_normal((32, 4)))
        w.submit(_task(_even(4, 8)))
        w.start()
        try:
            ev = events.get(timeout=60)
        finally:
            w.stop()
            w.join(timeout=10)
        assert isinstance(ev, WorkerFailed)
        assert "injected failure of a run" in ev.error
        assert w.dead and events.empty() and rec.calls == [(0, 16)]

    @pytest.mark.parametrize("answer,want_calls", [
        # one chunk's rows whatever is asked: asked again from the next
        (8, [(0, 16), (8, 16), (16, 24), (24, 32)]),
        # more rows than asked: those past the run are not used
        (24, [(0, 16), (16, 24), (24, 32)])])
    def test_a_backend_may_answer_with_other_rows(self, answer, want_calls):
        class Fixed(_Recorder):
            def compute_chunk(self, worker_id, shard_id, shard, r0, r1, x):
                self.calls.append((r0, r1))
                return np.pad(shard, ((0, 16), (0, 0)))[r0:r0 + answer] @ x

        rec = Fixed()
        task = _task(_even(4, 8))
        evs, shard = _run(rec, [task], 32)
        assert rec.calls == want_calls
        done = [e for e in evs if isinstance(e, ChunkDone)]
        assert [e.chunk_id for e in done] == [0, 1, 2, 3]
        for e, (_c, r0, r1) in zip(done, task.chunks):
            np.testing.assert_array_equal(e.result, shard[r0:r1] @ task.x)
        assert isinstance(evs[-1], WorkerDone) and not evs[-1].cancelled

    def test_an_answer_short_of_a_chunk_fails_the_worker(self):
        class Short(_Recorder):
            def compute_chunk(self, worker_id, shard_id, shard, r0, r1, x):
                return shard[r0:r0 + 4] @ x

        evs, _ = _run(Short(), [_task(_even(4, 8))], 32)
        (ev,) = evs
        assert isinstance(ev, WorkerFailed) and "rows 0:16" in ev.error

    def test_round_decodes_exactly_after_a_run_fails(self):
        """A worker whose backend raises on its first run of several
        chunks: every chunk of the run is failed over and the round still
        decodes exactly, as does the next."""
        n, k, chunks, d = 4, 2, 6, 120
        a = RNG.standard_normal((d, 8))
        x = RNG.standard_normal(8)

        class FailRuns:
            def compute_chunk(self, worker_id, shard_id, shard, r0, r1, x):
                if worker_id == 0 and r1 - r0 > d // (k * chunks):
                    raise RuntimeError("injected failure of a run")
                return shard[r0:r1] @ x

        eng = CodedExecutionEngine(
            ClusterConfig(n_workers=n, k=k, row_cost=1e-4),
            injector=NoSlowdown(), compute=FailRuns())
        try:
            data = eng.load_matrix(a, chunks=chunks)
            strat = GeneralS2C2(n, k, d, chunks=chunks)
            out = eng.matvec(data, x, strat)
            np.testing.assert_allclose(out.y, a @ x, rtol=1e-9, atol=1e-9)
            assert 0 in eng.dead
            assert any("injected failure of a run" in f
                       for f in out.metrics.worker_failures)
            out2 = eng.matvec(data, x, strat)
            np.testing.assert_allclose(out2.y, a @ x, rtol=1e-9, atol=1e-9)
        finally:
            eng.shutdown()


@pytest.mark.parametrize("chunks,want", [
    # 20 chunks a shard: 328 rows (65536 x 8192 on (12, 10)), 480 rows
    # (96000 x 5000), 25 rows (its transpose) and 2000 rows alike
    (20, [1, 2, 3, 4, 6, 8]),
    (17, [1, 2, 3, 4, 6, 8]),
    (5, [1, 2, 3]),
    (1, [1]),
    (40, [1, 2, 3, 4, 6, 8, 12, 16])])
def test_run_lengths_are_the_ladder(chunks, want):
    assert run_lengths(chunks) == want


def test_warm_runs_makes_every_call_a_round_can():
    """``warm_runs`` makes one call of each run length; a task of every
    chunk then calls with no row count the warm-up did not make."""
    rec = _Recorder()
    w = Worker(0, queue.Queue(), NoSlowdown(), compute=rec)
    w.install_shard("s", RNG.standard_normal((17 * 8, 4)))
    x = RNG.standard_normal(4)
    lengths = run_lengths(17)
    assert w.warm_runs("s", 8, 17, x) == len(lengths)
    assert rec.calls == [(0, 8 * n) for n in lengths]
    warmed = {r1 - r0 for r0, r1 in rec.calls}
    rec = _Recorder()
    _run(rec, [_task(_even(17, 8))], 17 * 8)
    assert {r1 - r0 for r0, r1 in rec.calls} <= warmed
    plain = Worker(1, queue.Queue(), NoSlowdown())
    plain.install_shard("s", np.ones((8, 4)))
    assert plain.warm_runs("s", 8, 1, x) == 0
