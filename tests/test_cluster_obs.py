"""Observability plane: tracer, Chrome export, metrics registry, logging.

Covers the PR-6 tentpole and satellites:

* :class:`Tracer` ring-buffer semantics (bounded capacity, enable/disable
  no-op, consistent snapshots);
* the trace SCHEMA under a forced-coverage run — every executed chunk has
  a well-formed span (start <= end) preceded by its enqueue, retracted
  chunks carry a retract record and are never executed afterwards without
  a fresh enqueue, and the exported JSON is valid Chrome trace-event
  format;
* trace/:class:`~repro.cluster.metrics.ServiceReport` consistency — the
  same steal / retract / round counts from both planes of a multi-tenant
  run;
* the metrics registry — families, lock-striped children, Prometheus text
  rendering, and the :meth:`ServiceReport.from_registry` bridge;
* the :class:`JobMetrics` negative-latency regression (errored jobs used
  to report ``t_start - t_submit`` with ``t_start == 0.0``);
* per-component loggers + :func:`configure_logging` — DEBUG lines
  cross-reference trace records by round/chunk id.
"""

import json
import logging
import math

import numpy as np
import pytest

from repro.cluster import (ClusterConfig, CodedExecutionEngine,
                           FailStopInjector, JobService, MatvecJob,
                           MetricsRegistry, NoSlowdown, TraceInjector,
                           Tracer, configure_logging)
from repro.cluster import obs
from repro.cluster.metrics import JobMetrics, ServiceReport
from repro.core.strategies import GeneralS2C2

RNG = np.random.default_rng(61)


def make_engine(n, k, injector, row_cost=2e-4, tracer=None, **kw):
    return CodedExecutionEngine(
        ClusterConfig(n_workers=n, k=k, row_cost=row_cost, **kw),
        injector=injector, tracer=tracer)


# ---------------------------------------------------------------------------
# tracer unit semantics
# ---------------------------------------------------------------------------

class TestTracer:
    def test_ring_buffer_keeps_newest(self):
        tr = Tracer(capacity=4)
        for i in range(10):
            tr.emit("k", chunk_id=i)
        assert len(tr) == 4
        assert [r.chunk_id for r in tr.snapshot()] == [6, 7, 8, 9]

    def test_disabled_emit_is_a_noop(self):
        tr = Tracer(enabled=False)
        tr.emit("k", worker=1)
        assert len(tr) == 0
        tr.enable()
        tr.emit("k", worker=1)
        assert len(tr) == 1
        tr.disable()
        tr.emit("k", worker=2)
        assert len(tr) == 1

    def test_record_fields_and_args(self):
        tr = Tracer()
        tr.emit("steal", worker=3, round_id=7, t=1.5, donor=1, n=2)
        (r,) = tr.snapshot()
        assert r.kind == "steal" and r.worker == 3 and r.round_id == 7
        assert r.t == 1.5 and r.chunk_id == -1 and r.dur == 0.0
        assert r.args == (("donor", 1), ("n", 2))   # sorted pairs

    def test_timestamps_are_monotonic_by_default(self):
        tr = Tracer()
        tr.emit("a")
        tr.emit("b")
        a, b = tr.snapshot()
        assert b.t >= a.t

    def test_clear(self):
        tr = Tracer()
        tr.emit("a")
        tr.clear()
        assert len(tr) == 0

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            Tracer(capacity=0)


# ---------------------------------------------------------------------------
# trace schema under a real engine run
# ---------------------------------------------------------------------------

def _spans_by_key(records):
    """chunk spans grouped by (worker, round, chunk), in emit order."""
    by = {}
    for r in records:
        if r.kind == obs.KIND_CHUNK:
            by.setdefault((r.worker, r.round_id, r.chunk_id), []).append(r)
    return by


class TestTraceSchema:
    def _run_traced(self, injector, n, k, chunks=8, rounds=3, d=240,
                    row_cost=2e-4):
        tr = Tracer()
        eng = make_engine(n, k, injector, row_cost=row_cost, tracer=tr)
        try:
            a = RNG.standard_normal((d, 16))
            x = RNG.standard_normal(16)
            data = eng.load_matrix(a, chunks=chunks)
            strat = GeneralS2C2(n, k, d, chunks=chunks)
            for _ in range(rounds):
                out = eng.matvec(data, x, strat)
                np.testing.assert_allclose(out.y, a @ x, rtol=1e-9,
                                           atol=1e-9)
        finally:
            eng.shutdown()
        return tr.snapshot(), eng

    def test_forced_coverage_run_has_well_formed_spans(self):
        """n-k workers fail-stopped from iteration 0: survivors execute
        every chunk, §4.3 waves fire, and every lifecycle invariant must
        hold."""
        records, _ = self._run_traced(FailStopInjector({0: 0, 1: 0}),
                                      n=5, k=3, chunks=6, d=180,
                                      row_cost=1e-4)
        enqueues = {}
        for r in records:
            if r.kind == obs.KIND_ENQUEUE:
                enqueues.setdefault(
                    (r.worker, r.round_id, r.chunk_id), []).append(r.t)
        spans = _spans_by_key(records)
        assert spans, "no chunk spans traced"
        for key, ss in spans.items():
            for s in ss:
                # well-formed span: start <= end
                assert s.dur >= 0.0
                # no orphans: every executed chunk was enqueued first
                assert key in enqueues, f"span without enqueue: {key}"
                assert min(enqueues[key]) <= s.t

    def test_retracted_chunks_never_execute_after_retraction(self):
        """Cold predictor + two heavy stragglers forces steals: each
        retract record must terminate that (worker, round, chunk) lifecycle
        unless a FRESH enqueue re-opens it (a re-dispatch to the same
        worker later is legal; execution after retraction without one is
        the bug this schema test exists to catch)."""
        n, k = 8, 6
        tr = np.ones((100, n))
        tr[:, 0] = tr[:, 1] = 0.05
        records, _ = self._run_traced(TraceInjector(tr), n=n, k=k,
                                      chunks=10, rounds=4, d=480)
        retracts = [r for r in records if r.kind == obs.KIND_RETRACT]
        steals = [r for r in records if r.kind == obs.KIND_STEAL]
        assert retracts and steals, "forcing scenario produced no steals"
        # every steal names its donor and the chunks moved
        for s in steals:
            args = dict(s.args)
            assert args["n"] >= 1 and len(args["chunks"]) == args["n"]
            assert args["donor"] != s.worker
        for rt in retracts:
            key = (rt.worker, rt.round_id, rt.chunk_id)
            later_spans = [r for r in records if r.kind == obs.KIND_CHUNK
                           and (r.worker, r.round_id, r.chunk_id) == key
                           and r.t >= rt.t]
            for s in later_spans:
                fresh = [r for r in records if r.kind == obs.KIND_ENQUEUE
                         and (r.worker, r.round_id, r.chunk_id) == key
                         and rt.t <= r.t <= s.t]
                assert fresh, (f"chunk {key} executed after retraction "
                               "with no re-enqueue")

    def test_round_phase_spans_cover_every_round(self):
        records, _ = self._run_traced(NoSlowdown(), n=4, k=3, chunks=6,
                                      rounds=3, d=120, row_cost=1e-5)
        rounds = {r.round_id for r in records if r.kind == obs.KIND_CHUNK}
        for kind in (obs.KIND_ROUND_PLAN, obs.KIND_ROUND_DISPATCH,
                     obs.KIND_ROUND_COLLECT, obs.KIND_ROUND_DECODE):
            have = {r.round_id for r in records if r.kind == kind}
            assert have == rounds, f"{kind} spans missing for {rounds - have}"
        # phases of one round are ordered: plan <= dispatch <= collect <= decode
        for rid in rounds:
            ts = {r.kind: r.t for r in records
                  if r.round_id == rid and r.kind in obs.MASTER_KINDS
                  and r.kind.startswith("round_")}
            assert ts[obs.KIND_ROUND_PLAN] <= ts[obs.KIND_ROUND_DISPATCH] \
                <= ts[obs.KIND_ROUND_COLLECT] <= ts[obs.KIND_ROUND_DECODE]

    def test_chunk_span_ends_when_compute_ends(self):
        """The row_cost top-up sleep is its own throttle span on the
        worker's compute lane, after the chunk span, not inside it."""
        records, _ = self._run_traced(NoSlowdown(), n=4, k=3, chunks=4,
                                      rounds=2, d=120, row_cost=2e-4)
        chunks = {(r.worker, r.round_id, r.chunk_id): r for r in records
                  if r.kind == obs.KIND_CHUNK}
        throttles = [r for r in records if r.kind == obs.KIND_THROTTLE]
        assert throttles, "no chunk was topped up"
        for th in throttles:
            c = chunks[(th.worker, th.round_id, th.chunk_id)]
            assert c.t + c.dur <= th.t and th.dur > 0.0
        events = obs.chrome_trace_events(records)
        lanes = {(ev["pid"], ev["tid"]) for ev in events
                 if ev.get("cat") == obs.KIND_THROTTLE}
        assert lanes and all(pid > 0 and tid == 0 for pid, tid in lanes)

    def test_exported_json_is_valid_chrome_trace(self, tmp_path):
        tr = Tracer()
        eng = make_engine(5, 3, FailStopInjector({0: 0, 1: 0}),
                          row_cost=1e-4, tracer=tr)
        try:
            a = RNG.standard_normal((180, 16))
            data = eng.load_matrix(a, chunks=6)
            eng.matvec(data, np.ones(16), GeneralS2C2(5, 3, 180, chunks=6))
            path = tmp_path / "trace.json"
            n_events = eng.dump_trace(path)
        finally:
            eng.shutdown()
        doc = json.loads(path.read_text())
        events = doc["traceEvents"]
        assert len(events) == n_events > 0
        valid_ph = {"X", "i", "C", "M"}
        for ev in events:
            assert ev["ph"] in valid_ph
            assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
            assert "name" in ev
            if ev["ph"] != "M":
                assert ev["ts"] >= 0.0          # rebased to the first record
            if ev["ph"] == "X":
                assert ev["dur"] >= 0.0
            if ev["ph"] == "i":
                assert ev["s"] in ("t", "p", "g")
            json.dumps(ev)                      # every field serializable
        # metadata names both planes
        names = {ev["args"]["name"] for ev in events
                 if ev["ph"] == "M" and ev["name"] == "process_name"}
        assert "master" in names
        assert any(n.startswith("worker") for n in names)

    def test_injected_and_observed_speeds_are_annotated(self):
        n, k = 4, 3
        tr = np.ones((50, n))
        tr[:, 0] = 0.25
        records, _ = self._run_traced(TraceInjector(tr), n=n, k=k,
                                      chunks=6, rounds=2, d=120,
                                      row_cost=1e-4)
        inj = [r for r in records if r.kind == obs.KIND_INJ_SPEED]
        obs_ = [r for r in records if r.kind == obs.KIND_OBS_SPEED]
        assert {r.worker for r in inj} == set(range(n))
        assert obs_, "no observed speeds traced"
        # the injected slowdown of worker 0 is visible in the annotation
        assert any(r.worker == 0 and dict(r.args)["speed"] == 0.25
                   for r in inj)


# ---------------------------------------------------------------------------
# trace <-> ServiceReport consistency (the acceptance criterion)
# ---------------------------------------------------------------------------

class TestTraceReportConsistency:
    def test_multi_tenant_counts_match(self):
        """A multi-tenant run (straggler-hit pool, coalescing off so every
        job round is its own engine round): the trace and the report must
        agree on round / steal / retract counts exactly."""
        n, k, chunks, d = 8, 6, 10, 480
        trc = np.ones((100, n))
        trc[:, 0] = trc[:, 1] = 0.05
        tracer = Tracer()
        eng = make_engine(n, k, TraceInjector(trc), tracer=tracer)
        svc = JobService(eng, max_inflight=3, coalesce=False)
        try:
            rng = np.random.default_rng(7)
            mats = [rng.standard_normal((d, 24)) for _ in range(3)]
            strat = GeneralS2C2(n, k, d, chunks=chunks)
            handles = [svc.submit(MatvecJob(
                a, [rng.standard_normal(24) for _ in range(2)], strat,
                chunks=chunks)) for a in mats]
            svc.drain(timeout=120)
            for h, a in zip(handles, mats):
                want = np.stack([a @ x for x in h.job.xs])
                np.testing.assert_allclose(h.output, want, rtol=1e-9,
                                           atol=1e-9)
            rep = svc.report()
        finally:
            svc.close()
            eng.shutdown()
        records = tracer.snapshot()
        n_steals = sum(1 for r in records if r.kind == obs.KIND_STEAL)
        n_retract = sum(1 for r in records if r.kind == obs.KIND_RETRACT)
        n_rounds = sum(1 for r in records
                       if r.kind == obs.KIND_ROUND_DECODE)
        n_waves = sum(1 for r in records if r.kind == obs.KIND_WAVE)
        assert rep.n_jobs == 3
        assert n_rounds == rep.n_rounds        # coalesce off: 1 job round
        #                                        == 1 engine round
        assert n_steals == rep.total_steals >= 1
        assert n_retract == rep.total_retracted >= 1
        waves_reported = sum(r.reassign_waves for j in [h.metrics
                                                        for h in handles]
                             for r in j.rounds)
        assert n_waves == waves_reported
        # the registry agrees with both planes
        reg = eng.registry
        assert int(reg.value("s2c2_rounds_total")) == rep.n_rounds
        assert int(reg.value("s2c2_steals_total")) == rep.total_steals
        assert int(reg.value("s2c2_chunks_retracted_total")) == \
            rep.total_retracted
        assert int(reg.value("s2c2_jobs_total")) == rep.n_jobs


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counter_semantics(self):
        reg = MetricsRegistry()
        c = reg.counter("requests_total", "reqs", ("code",))
        c.labels(code=200).inc()
        c.labels(code=200).inc(2)
        c.labels(code=500).inc()
        assert c.labels(code=200).value == 3
        assert c.total() == 4
        with pytest.raises(ValueError):
            c.labels(code=200).inc(-1)          # counters only go up

    def test_gauge_semantics(self):
        reg = MetricsRegistry()
        g = reg.gauge("inflight")
        g.set(5)
        g.inc()
        g.dec(2)
        assert g.value == 4

    def test_histogram_buckets_and_quantile(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.05, 0.5, 5.0):
            h.observe(v)
        assert h.count == 4
        assert h.sum == pytest.approx(5.6)
        q50 = h.quantile(50)
        assert 0.0 <= q50 <= 1.0                # within the first two buckets
        assert h.quantile(100) <= 10.0

    def test_get_or_create_is_idempotent_and_conflict_checked(self):
        reg = MetricsRegistry()
        a = reg.counter("x_total", "x", ("l",))
        assert reg.counter("x_total", "x", ("l",)) is a
        with pytest.raises(ValueError):
            reg.gauge("x_total")                # kind conflict
        with pytest.raises(ValueError):
            reg.counter("x_total", "x", ("other",))   # label-schema conflict

    def test_unlabeled_access_of_labeled_family_raises(self):
        reg = MetricsRegistry()
        c = reg.counter("y_total", "y", ("l",))
        with pytest.raises(ValueError):
            c.inc()

    def test_prometheus_render_format(self):
        reg = MetricsRegistry()
        reg.counter("jobs_total", "jobs done", ("status",)) \
            .labels(status="ok").inc(3)
        reg.gauge("inflight", "in flight").set(2)
        h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        text = reg.render()
        lines = text.strip().splitlines()
        assert "# TYPE jobs_total counter" in lines
        assert 'jobs_total{status="ok"} 3' in lines
        assert "# TYPE inflight gauge" in lines
        assert "inflight 2" in lines
        assert "# TYPE lat_seconds histogram" in lines
        assert 'lat_seconds_bucket{le="0.1"} 1' in lines
        assert 'lat_seconds_bucket{le="1"} 2' in lines      # cumulative
        assert 'lat_seconds_bucket{le="+Inf"} 2' in lines
        assert "lat_seconds_count 2" in lines
        # label values are escaped
        reg.counter("esc_total", "", ("v",)).labels(v='a"b\\c').inc()
        assert r'esc_total{v="a\"b\\c"} 1' in reg.render()

    def test_log_buckets_are_log_spaced(self):
        b = obs.log_buckets(1e-3, 1.0, per_decade=1)
        assert b == pytest.approx((1e-3, 1e-2, 1e-1, 1.0))
        assert list(obs.DEFAULT_BUCKETS) == sorted(obs.DEFAULT_BUCKETS)


# ---------------------------------------------------------------------------
# JobMetrics negative-latency regression + from_registry bridge
# ---------------------------------------------------------------------------

class TestJobMetricsRegression:
    def test_unstamped_job_has_nan_not_negative_timings(self):
        """Regression: a job erroring before the scheduler stamped t_start
        reported queue_wait = 0.0 - t_submit (a huge negative number)."""
        m = JobMetrics(job_id=1, kind="matvec", strategy="GeneralS2C2",
                       t_submit=1234.5, error="boom")
        assert math.isnan(m.queue_wait)
        assert math.isnan(m.latency)
        assert math.isnan(m.service_time)

    def test_from_jobs_excludes_errored_jobs_from_percentiles(self):
        ok = JobMetrics(job_id=1, kind="matvec", strategy="S",
                        t_submit=100.0, t_start=100.5, t_done=101.0)
        bad = JobMetrics(job_id=2, kind="matvec", strategy="S",
                         t_submit=100.0, error="boom")
        rep = ServiceReport.from_jobs([ok, bad], wall_time=2.0)
        assert rep.n_jobs == 2                  # errored jobs still counted
        assert rep.p50_latency == pytest.approx(1.0)
        assert rep.p99_latency == pytest.approx(1.0)
        assert rep.p50_queue_wait == pytest.approx(0.5)
        assert rep.by_strategy["S"]["p50_latency"] == pytest.approx(1.0)
        assert rep.by_strategy["S"]["mean_service_time"] == \
            pytest.approx(0.5)
        # nothing negative anywhere
        assert rep.p99_latency >= 0 and rep.p99_queue_wait >= 0

    def test_half_stamped_job_clamps_to_zero_not_negative(self):
        m = JobMetrics(job_id=3, kind="matvec", strategy="S",
                       t_submit=100.0, t_start=99.9, t_done=100.2)
        assert m.queue_wait == 0.0              # clock skew clamps at zero
        assert m.latency == pytest.approx(0.2)

    def test_from_registry_bridges_service_totals(self):
        eng = make_engine(4, 3, NoSlowdown(), row_cost=1e-5)
        svc = JobService(eng, max_inflight=2)
        try:
            rng = np.random.default_rng(9)
            a = rng.standard_normal((120, 16))
            for _ in range(3):
                svc.submit(MatvecJob(a, [rng.standard_normal(16)],
                                     GeneralS2C2(4, 3, 120, chunks=6),
                                     chunks=6))
            svc.drain(timeout=60)
            rep = svc.report()
            bridged = ServiceReport.from_registry(
                eng.registry, rep.wall_time, max_inflight=2,
                peak_inflight=svc.peak_inflight)
        finally:
            svc.close()
            eng.shutdown()
        assert bridged.n_jobs == rep.n_jobs == 3
        assert bridged.total_steals == rep.total_steals
        assert bridged.total_retracted == rep.total_retracted
        assert bridged.wall_time == rep.wall_time
        # bucket-interpolated percentiles approximate the exact ones
        assert bridged.p50_latency > 0
        assert "GeneralS2C2" in bridged.by_strategy
        assert bridged.by_strategy["GeneralS2C2"]["jobs"] == 3


# ---------------------------------------------------------------------------
# per-component logging
# ---------------------------------------------------------------------------

class TestLogging:
    def test_component_loggers_are_children(self):
        import repro.cluster.master as master
        import repro.cluster.service as service
        import repro.cluster.worker as worker
        assert master.logger.name == "repro.cluster.master"
        assert worker.logger.name == "repro.cluster.worker"
        assert service.logger.name == "repro.cluster.service"

    def test_configure_logging_is_idempotent(self):
        root = configure_logging(logging.INFO)
        n = len(root.handlers)
        configure_logging(logging.DEBUG)
        assert len(root.handlers) == n          # replaced, not stacked
        assert root.level == logging.DEBUG
        for h in list(root.handlers):
            if getattr(h, obs._LOG_MARK, False):
                root.removeHandler(h)

    def test_debug_logs_cross_reference_trace_records(self, caplog):
        """A forced-steal run at DEBUG: every steal trace record has a log
        line naming the same round (trace and logs cross-reference)."""
        n, k = 8, 6
        trc = np.ones((100, n))
        trc[:, 0] = trc[:, 1] = 0.05
        tracer = Tracer()
        with caplog.at_level(logging.DEBUG, logger="repro.cluster"):
            eng = make_engine(n, k, TraceInjector(trc), tracer=tracer)
            try:
                a = RNG.standard_normal((480, 16))
                data = eng.load_matrix(a, chunks=10)
                strat = GeneralS2C2(n, k, 480, chunks=10)
                for _ in range(4):
                    eng.matvec(data, np.ones(16), strat)
            finally:
                eng.shutdown()
        steals = [r for r in tracer.snapshot() if r.kind == obs.KIND_STEAL]
        assert steals, "forcing scenario produced no steals"
        steal_logs = [rec for rec in caplog.records
                      if rec.name == "repro.cluster.master"
                      and "stole chunks" in rec.getMessage()]
        assert len(steal_logs) == len(steals)
        logged_rounds = {int(m.getMessage().split()[1].rstrip(":"))
                         for m in steal_logs}
        assert logged_rounds == {r.round_id for r in steals}


# ---------------------------------------------------------------------------
# overhead guard: tracing off must not change behavior
# ---------------------------------------------------------------------------

class TestOverheadGuard:
    def test_untraced_engine_emits_nothing(self):
        eng = make_engine(4, 3, NoSlowdown(), row_cost=1e-5)
        try:
            assert not eng.tracer.enabled
            a = RNG.standard_normal((120, 16))
            data = eng.load_matrix(a, chunks=6)
            eng.matvec(data, np.ones(16), GeneralS2C2(4, 3, 120, chunks=6))
            assert len(eng.tracer) == 0
        finally:
            eng.shutdown()

    def test_tracer_can_be_toggled_mid_engine(self):
        tracer = Tracer(enabled=False)
        eng = make_engine(4, 3, NoSlowdown(), row_cost=1e-5, tracer=tracer)
        try:
            a = RNG.standard_normal((120, 16))
            data = eng.load_matrix(a, chunks=6)
            strat = GeneralS2C2(4, 3, 120, chunks=6)
            eng.matvec(data, np.ones(16), strat)
            assert len(tracer) == 0
            tracer.enable()
            eng.matvec(data, np.ones(16), strat)
            assert len(tracer) > 0
        finally:
            eng.shutdown()
