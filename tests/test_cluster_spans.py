"""Spans on the profiler's clock and the per-chunk cost counters.

* :class:`repro.cluster.obs.span` makes no profiler annotation without a
  recording session, never imports JAX itself, and, under
  ``jax.profiler.trace``, writes ``s2c2.*`` host events into the
  ``.xplane.pb`` nested as the engine nests them;
* a backend's :func:`~repro.cluster.obs.charge_start` /
  :func:`~repro.cluster.obs.charge_end` split its call into thread-CPU and
  off-CPU time, and the worker's record excludes whatever a wrapper does
  around the call (the benchmark's straggler sleep);
* the round driver sums the records of the chunk results it receives into
  :class:`~repro.cluster.metrics.RoundMetrics` and the registry, and a
  coalesced follower's copy zeroes them;
* the benchmark's three readers of these counters.
"""

import glob
import pathlib
import queue
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

from repro.cluster import (ClusterConfig, CodedExecutionEngine, JobService,
                           MatvecJob, NoSlowdown, Worker, kernel_backend)
from repro.cluster import obs
from repro.cluster.metrics import RoundMetrics
from repro.cluster.service import _follower_metrics
from repro.cluster.worker import ChunkDone, ChunkTask
from repro.core.strategies import GeneralS2C2

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.backends import TimedBackend  # noqa: E402
from bench.manifest import layer_reader  # noqa: E402

RNG = np.random.default_rng(14)


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; counts instances."""

    recording = False
    made = []

    def __init__(self, name, **ids):
        self.name, self.ids = name, ids
        _FakeAnnotation.made.append(self)

    @classmethod
    def is_enabled(cls):
        return cls.recording

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args):
        self.ids.update(args)


@pytest.fixture
def fake_annotation(monkeypatch):
    _FakeAnnotation.made = []
    _FakeAnnotation.recording = False
    monkeypatch.setattr(obs, "_annotation_cls", _FakeAnnotation)
    return _FakeAnnotation


class TestSpan:
    def test_no_annotation_without_a_recording_session(self,
                                                       fake_annotation):
        with obs.span("s2c2.worker.chunk", worker=1, round_id=2,
                      chunk_id=3) as s:
            assert not s.recording
        assert fake_annotation.made == []
        assert 0.0 < s.t0 <= s.t1 and s.dur == s.t1 - s.t0

    def test_annotation_carries_the_ids_given(self, fake_annotation):
        fake_annotation.recording = True
        with obs.span("s2c2.round.collect", round_id=5) as s:
            assert s.recording
            s.note(waves=2)
        with obs.span("s2c2.job", job=7):
            pass
        (collect, job) = fake_annotation.made
        assert collect.name == "s2c2.round.collect"
        assert collect.ids == {"round": 5, "waves": 2}
        assert job.ids == {"job": 7}

    def test_real_profiler_off_records_nothing(self):
        with obs.span("s2c2.job", job=1) as s:
            assert not s.recording

    def test_tracer_record_from_the_span_stamps(self):
        tr = obs.Tracer()
        with obs.span("s2c2.round.decode", tr, obs.KIND_ROUND_DECODE,
                      round_id=4, strategy="X") as s:
            s.note(extra=1)
        (r,) = tr.snapshot()
        assert (r.kind, r.round_id, r.t, r.dur) == \
            (obs.KIND_ROUND_DECODE, 4, s.t0, s.dur)
        assert dict(r.args) == {"strategy": "X", "extra": 1}
        # a disabled tracer, a span without a kind, and a raising block
        # record nothing
        off = obs.Tracer(enabled=False)
        with obs.span("s2c2.round.plan", off, obs.KIND_ROUND_PLAN):
            pass
        with obs.span("s2c2.backend.x", tr):
            pass
        with pytest.raises(RuntimeError):
            with obs.span("s2c2.worker.chunk", tr, obs.KIND_CHUNK):
                raise RuntimeError("backend")
        assert len(off) == 0 and len(tr) == 1

    def test_the_helper_never_imports_jax(self, tmp_path):
        """obs.py loaded on its own: spans and cost records work in a
        process that has no JAX, and leave it without one."""
        code = (
            "import importlib.util, sys\n"
            f"spec = importlib.util.spec_from_file_location('obs', "
            f"{str(ROOT / 'src/repro/cluster/obs.py')!r})\n"
            "obs = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(obs)\n"
            "tr = obs.Tracer()\n"
            "obs.open_record()\n"
            "with obs.span('s2c2.worker.chunk', tr, obs.KIND_CHUNK,\n"
            "              worker=0) as s:\n"
            "    obs.charge_end(obs.charge_start())\n"
            "cost = obs.CallCost(*obs.close_record())\n"
            "assert not s.recording and not obs.recording()\n"
            "assert len(tr) == 1 and cost.calls == cost.cpu_calls == 1\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n"
            "print('ok')\n")
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.strip() == "ok"


def _engine(n, k, compute, row_cost=1e-5, tracer=None):
    return CodedExecutionEngine(
        ClusterConfig(n_workers=n, k=k, row_cost=row_cost), NoSlowdown(),
        compute=compute, tracer=tracer)


class TestSpansInTheProfilerTrace:
    def test_s2c2_spans_nest_in_the_host_plane(self, tmp_path):
        """A traced service job through KernelBackend (interpret mode):
        each span name appears on the host plane; backend spans sit
        inside a worker.chunk on the same thread, a throttle follows its
        chunk there, and the round phases lie inside the job span."""
        import jax
        from jax.profiler import ProfileData
        n, k, d, cols, chunks = 4, 3, 96, 32, 2
        # row_cost large enough that every chunk is topped up by a sleep
        eng = _engine(n, k, kernel_backend(), row_cost=2e-4)
        svc = JobService(eng, max_inflight=1, coalesce=False)
        try:
            a = RNG.standard_normal((d, cols))
            strat = GeneralS2C2(n, k, d, chunks=chunks)
            data = svc.share_matrix(a, chunks=chunks)

            def job():
                x = RNG.standard_normal(cols)
                h = svc.submit(MatvecJob(a, [x], strat, chunks=chunks,
                                         data=data))
                assert h.wait(60) and h.metrics.error is None
                np.testing.assert_allclose(h.output[0], a @ x, rtol=1e-4,
                                           atol=1e-4)
                return h

            job()                                # compile outside the trace
            with jax.profiler.trace(str(tmp_path)):
                h = job()
        finally:
            svc.close()
            eng.shutdown()
        path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)
        assert path, "no trace written"
        prof = ProfileData.from_file(path[0])
        lines = []
        for plane in prof.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events
                           if e.name.startswith("s2c2.")]
                    if evs:
                        lines.append(evs)
        every = [e for evs in lines for e in evs]
        names = {e[0] for e in every}
        assert names == {
            "s2c2.job", "s2c2.round.plan", "s2c2.round.dispatch",
            "s2c2.round.collect", "s2c2.round.decode", "s2c2.worker.chunk",
            "s2c2.worker.throttle", "s2c2.backend.x", "s2c2.backend.dispatch",
            "s2c2.backend.device_wait", "s2c2.backend.copy_back"}, names
        rid = h.metrics.rounds[0].round_id
        (job_ev,) = [e for e in every if e[0] == "s2c2.job"]
        assert job_ev[3]["job"] == h.metrics.job_id
        for e in every:
            if e[0].startswith("s2c2.round."):
                assert e[3]["round"] == rid
                assert job_ev[1] <= e[1] and e[2] <= job_ev[2], e
        for evs in lines:
            chunks_ = [e for e in evs if e[0] == "s2c2.worker.chunk"]
            for e in evs:
                if e[0].startswith("s2c2.backend."):
                    assert any(c[1] <= e[1] and e[2] <= c[2]
                               for c in chunks_), e
                if e[0] == "s2c2.worker.throttle":
                    (c,) = [c for c in chunks_
                            if c[3]["chunk"] == e[3]["chunk"]]
                    assert c[2] <= e[1], "throttle overlaps its chunk"
            for c in chunks_:
                assert c[3]["round"] == rid and "worker" in c[3]


class _BusyThenBlock:
    """A backend that spends ``cpu_s`` on the CPU, then blocks ``wait_s``."""

    def __init__(self, cpu_s, wait_s):
        self.cpu_s, self.wait_s = cpu_s, wait_s

    def compute_chunk(self, worker_id, shard_id, shard, r0, r1, x):
        start = obs.charge_start()
        c0 = time.thread_time()
        while time.thread_time() - c0 < self.cpu_s:
            pass
        threading.Event().wait(self.wait_s)
        obs.charge_end(start)
        return shard[r0:r1] @ x


def _runs(chunk_ids):
    """Backend calls for one task's chunks, queued in this order: each call
    takes consecutive chunk ids, at most half (rounded up) of those left."""
    calls = i = 0
    while i < len(chunk_ids):
        cap, m = -(-(len(chunk_ids) - i) // 2), 1
        while m < cap and chunk_ids[i + m] == chunk_ids[i + m - 1] + 1:
            m += 1
        calls, i = calls + 1, i + m
    return calls


def _run_worker(compute, chunks=2, rows=8):
    """One Worker computing ``chunks`` chunks; returns its ChunkDones."""
    events = queue.Queue()
    w = Worker(0, events, NoSlowdown(), compute=compute)
    shard = RNG.standard_normal((rows * chunks, 16))
    w.install_shard("s", shard)
    w.start()
    try:
        w.submit(ChunkTask(round_id=1, iteration=0, shard_id="s",
                           chunks=[(c, c * rows, (c + 1) * rows)
                                   for c in range(chunks)],
                           x=RNG.standard_normal(16), row_cost=0.0,
                           cancel=threading.Event()))
        done = []
        while len(done) < chunks:
            ev = events.get(timeout=60)
            if isinstance(ev, ChunkDone):
                done.append(ev)
    finally:
        w.stop()
        w.join(timeout=10)
    assert not w.is_alive()
    return done


class TestCostRecords:
    def test_busy_then_block_splits_into_cpu_and_off_cpu(self,
                                                          monkeypatch):
        """A thread's first call has its CPU read, and one in CPU_SAMPLE
        after it; the wall time of every call is read.  A call's record
        rides the first chunk of its run."""
        monkeypatch.setattr(obs, "CPU_SAMPLE", 4)
        done = _run_worker(_BusyThenBlock(cpu_s=0.01, wait_s=0.015),
                           chunks=32)
        costs = [obs.CallCost(*ev.cost) for ev in done]
        # runs of 16, 8, 4, 2, 1 and 1 chunks: half the queue each time
        heads = [0, 16, 24, 28, 30, 31]
        assert [i for i, c in enumerate(costs) if c != obs.NO_COST] == heads
        calls = [costs[i] for i in heads]
        assert [c.cpu_calls for c in calls] == [1, 0, 0, 0, 1, 0]
        for c in calls:
            assert c.calls == 1 and 0.025 <= c.wall_s < 1.0
            if c.cpu_calls:
                assert c.cpu_s >= 0.01
                assert c.wall_s - c.cpu_s >= 0.0125
            else:
                assert c.cpu_s == 0.0

    def test_numpy_backend_charges_nothing(self):
        for ev in _run_worker(lambda a, x: a @ x):
            assert obs.CallCost(*ev.cost) == obs.NO_COST

    def test_record_excludes_the_wrappers_sleep(self):
        """KernelBackend under the benchmark's TimedBackend, whose mix
        sleeps as long again after each call: every record is at most the
        TimedBackend call's compute time, and under the whole call.  Three
        queued chunks are two calls: a run of two, then one."""
        mix = types.SimpleNamespace(slowdown=lambda worker: 1.0)
        timed = TimedBackend(kernel_backend(), mix)
        done = _run_worker(timed, chunks=3)
        calls = timed.records()
        assert len(calls) == 2 and len(done) == 3
        costs = [obs.CallCost(*ev.cost) for ev in done]
        assert costs[1] == obs.NO_COST
        for c, (t0, t1, t2, _w, asked) in zip([costs[0], costs[2]], calls):
            assert c.calls == 1
            assert c.wall_s <= t1 - t0
            assert asked > 0.0 and c.wall_s < t2 - t0
        assert costs[0].cpu_calls == 1
        assert 0.0 < costs[0].cpu_s <= costs[0].wall_s

    def test_round_sums_the_chunk_results_it_receives(self):
        """With no steals and no §4.3 waves every dispatched chunk is
        computed once and received, so the calls the rounds count are
        exactly the runs of each dispatched task."""
        n, k, d, chunks = 4, 3, 96, 4
        timed = TimedBackend(kernel_backend(), None)
        tr = obs.Tracer()
        eng = CodedExecutionEngine(
            ClusterConfig(n_workers=n, k=k, row_cost=1e-5,
                          enable_stealing=False, max_reassign_waves=0),
            NoSlowdown(), compute=timed, tracer=tr)
        try:
            a = RNG.standard_normal((d, 32))
            data = eng.load_matrix(a, chunks=chunks)
            strat = GeneralS2C2(n, k, d, chunks=chunks)
            ms = [eng.matvec(data, RNG.standard_normal(32), strat).metrics
                  for _ in range(3)]
        finally:
            eng.shutdown()
        reg = eng.registry
        calls = sum(m.chunk_calls for m in ms)
        tasks = {}
        for r in tr.snapshot():
            if r.kind == obs.KIND_ENQUEUE:
                tasks.setdefault((r.worker, r.round_id, r.t),
                                 []).append(r.chunk_id)
        assert calls == sum(_runs(ids) for ids in tasks.values()) > 0
        assert int(reg.value("s2c2_chunks_done_total")) == \
            sum(len(ids) for ids in tasks.values()) > calls
        assert calls == len(timed.records())
        cpu_calls = sum(m.chunk_cpu_calls for m in ms)
        assert 0 < cpu_calls <= calls
        for m in ms:
            assert 0.0 < m.chunk_wall_s and 0.0 < m.host_cpu_s
            assert (m.chunk_cpu_s > 0.0) == (m.chunk_cpu_calls > 0)
        assert reg.value("s2c2_backend_calls_total") == calls
        assert reg.value("s2c2_backend_cpu_calls_total") == cpu_calls
        assert reg.value("s2c2_backend_wall_seconds_total") == \
            pytest.approx(sum(m.chunk_wall_s for m in ms))
        assert reg.value("s2c2_backend_cpu_seconds_total") == \
            pytest.approx(sum(m.chunk_cpu_s for m in ms))
        assert reg.value("s2c2_round_driver_cpu_seconds_total") == \
            pytest.approx(sum(m.host_cpu_s for m in ms))
        # the round-phase records come from the same stamps as the metrics
        decodes = {r.round_id: r for r in tr.snapshot()
                   if r.kind == obs.KIND_ROUND_DECODE}
        for m in ms:
            assert decodes[m.round_id].dur == m.decode_time

    def test_follower_copy_zeroes_the_host_cost(self):
        m = _metrics(chunk_calls=20, chunk_wall_s=0.1, chunk_cpu_calls=3,
                     chunk_cpu_s=0.02, host_cpu_s=0.005)
        f = _follower_metrics(m)
        assert (f.chunk_calls, f.chunk_wall_s, f.chunk_cpu_calls,
                f.chunk_cpu_s, f.host_cpu_s) == (0, 0.0, 0, 0.0, 0.0)
        assert f.makespan == m.makespan


def _metrics(**kw):
    z = np.zeros(3)
    return RoundMetrics(round_id=1, strategy="GeneralS2C2", makespan=1.0,
                        compute_time=0.9, decode_time=0.1, useful_rows=z,
                        wasted_rows=z, speeds_measured=z,
                        planned_makespan=1.0, **kw)


class TestReaders:
    ROUNDS = [dict(chunk_calls=200, chunk_wall_s=1.0, chunk_cpu_calls=25,
                   chunk_cpu_s=0.01, host_cpu_s=0.02),
              dict(chunk_calls=200, chunk_wall_s=1.4, chunk_cpu_calls=15,
                   chunk_cpu_s=0.006, host_cpu_s=0.04),
              # a coalesced follower's ride-along copy
              dict()]

    @pytest.mark.parametrize("metric, want", [
        ("backend.chunk_cpu_us", 0.016 / 40 * 1e6),
        ("backend.chunk_offcpu_us", (2.4 / 400 - 0.016 / 40) * 1e6),
        ("engine.round_cpu_ms", 0.03 * 1e3)])
    def test_values(self, metric, want):
        run = types.SimpleNamespace(rounds=[_metrics(**r)
                                            for r in self.ROUNDS])
        assert layer_reader(metric)(run) == pytest.approx(want)

    @pytest.mark.parametrize("metric", ["backend.chunk_cpu_us",
                                        "backend.chunk_offcpu_us",
                                        "engine.round_cpu_ms"])
    def test_none_without_calls(self, metric):
        read = layer_reader(metric)
        assert read(types.SimpleNamespace(rounds=[])) is None
        assert read(types.SimpleNamespace(rounds=[_metrics()])) is None
        # rounds of a program without these counters
        old = types.SimpleNamespace(decode_time=0.1)
        assert read(types.SimpleNamespace(rounds=[old])) is None


def test_gap_labels_by_innermost_span():
    """``scripts/gap_spans.py``: each thread is in its innermost open span;
    a gap is labelled by the worker threads' largest share of it."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "gap_spans", ROOT / "scripts" / "gap_spans.py")
    gs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gs)
    driver = [("s2c2.job", 0, 100), ("s2c2.round.collect", 10, 90)]
    worker = [("s2c2.worker.chunk", 20, 60),
              ("s2c2.backend.device_wait", 30, 50),
              ("s2c2.worker.chunk", 70, 80)]
    assert gs.innermost(worker) == [
        ("s2c2.worker.chunk", 20, 30), ("s2c2.backend.device_wait", 30, 50),
        ("s2c2.worker.chunk", 50, 60), ("-", 60, 70),
        ("s2c2.worker.chunk", 70, 80)]
    (g,) = gs.label_gaps([(25, 65)], [driver, worker])
    assert g["label"] == "s2c2.backend.device_wait"
    assert [n for n, _ in g["workers"]] == [
        "s2c2.backend.device_wait", "s2c2.worker.chunk", "-"]
    assert [t for _, t in g["workers"]] == pytest.approx([20e-9, 15e-9,
                                                          5e-9])
    assert [n for n, _ in g["others"]] == ["s2c2.round.collect"]
    assert g["others"][0][1] == pytest.approx(40e-9)
    assert g["gap_s"] == pytest.approx(40e-9)
