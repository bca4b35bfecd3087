"""The gradient-descent cell of the benchmark, checked on the CPU.

A tiny root holds the benchmark's files plus a tiny configuration of the
``planted_logistic`` matrix and a tiny ``logreg_gd`` traffic mix, added
the way a change adds a cell: new files and a ``workloads`` entry.  The
cell runs ``correct`` through the whole harness but its look for a chip;
the control and a planted gradient fault read ``correct`` false; the
three per-layer readers of the cell read a synthetic run.
"""

import json
import threading

import numpy as np
import pytest

from bench_tiny import ROOT, run_tiny, tiny_root

from bench.backends import HighPrecisionControl
from bench.harness import RunRecord
from bench.manifest import find_module, layer_reader, load_cell
from bench.roofline import chunk_bytes, peaks
from repro.cluster.metrics import JobMetrics, RoundMetrics

CELL = "gd96k-it2-strag2"
READERS = ("job.gd_step_ms", "engine.grad_round_ms", "kernel.gd_roofline")

TINY_GD = {
    "name": "tiny-gd", "source": "test", "matrix": "planted_logistic",
    "rows": 2400, "cols": 200,
    "code": {"n": 12, "k": 10, "generator": "systematic_cauchy", "chunks": 4},
    "precision": {"host": "float64", "shards": "float32",
                  "matmul": "highest", "decode": "float64",
                  "gd_step": "float64"},
    # median_rel_err: over 8 seeds the program read 5.8e-8 – 7.4e-8 here
    # (interpret mode, float32 at HIGHEST), the control 9.7e-7 – 1.2e-6
    "limits": {"max_rel_err": 1e-3, "median_rel_err": 3e-7},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny root of ``bench_tiny`` plus the cell ``tiny-gd.it2``."""
    root = tiny_root(tmp_path_factory.mktemp("bench_root"))
    (root / "bench" / "configs" / "tiny-gd.json").write_text(
        json.dumps(TINY_GD))
    traffic = json.loads(
        (ROOT / "bench" / "traffic" / "gd2-strag2.json").read_text())
    traffic.update(clients=2, settle_jobs=1, w0_pool=16, check_sample=4)
    (root / "bench" / "traffic" / "tiny-gd2.json").write_text(
        json.dumps(traffic))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tiny-gd", "source": "test",
                           "file": "bench/configs/tiny-gd.json",
                           "reduced": [], "why": "tiny"})
    man["workloads"].append({"name": "tiny-gd.it2", "config": "tiny-gd",
                             "traffic": "tiny-gd2", "chips": 1,
                             "why": "tiny"})
    for m in man["per_layer"]:
        if m["name"] in READERS:
            m["workloads"].append("tiny-gd.it2")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def test_cell_is_in_the_manifest():
    cell = load_cell(CELL)
    assert cell.config["rows"] == 96000 and cell.config["cols"] == 5000
    assert cell.traffic["job"] == "logreg_gd" and cell.mix["n_stragglers"] == 2
    assert set(READERS) <= {m["name"] for m in cell.per_layer}


def test_tiny_gd_cell_runs_correct(root):
    r = run_tiny(root, "tiny-gd.it2")
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    c = r["compared"]["max_rel_err"]
    assert 0.0 < c["value"] <= c["limit"]
    assert r["metrics"]["products_per_s"]["value"] > 0


def test_control_is_not_correct(root):
    r = run_tiny(root, "tiny-gd.it2",
                 backend_wrap=lambda inner: HighPrecisionControl())
    assert r["correct"] is False
    med = r["compared"]["median_rel_err"]
    assert med["value"] > med["limit"]


class StaleResidual:
    """A planted gradient fault: a chunk of the transpose (a shard whose
    width is the matrix's row count) is computed against the residual the
    worker saw before, the previous iteration's, where it saw one."""

    def __init__(self, inner, rows: int):
        self.inner = inner
        self.rows = rows
        self._lock = threading.Lock()
        self._last = {}

    def compute_chunk(self, worker_id, shard_id, shard, r0, r1, x):
        if shard.shape[1] == self.rows:
            key = (worker_id, shard_id)
            with self._lock:
                prev = self._last.setdefault(key, [x, x])
                if prev[1] is not x:
                    prev[:] = [prev[1], x]
                x = prev[0]
        return self.inner.compute_chunk(worker_id, shard_id, shard, r0, r1, x)

    def drop_shard(self, worker_id, shard_id):
        self.inner.drop_shard(worker_id, shard_id)


def test_stale_residual_fault_is_not_correct(root):
    r = run_tiny(root, "tiny-gd.it2", backend_wrap=lambda inner: StaleResidual(
        inner, TINY_GD["rows"]))
    assert r["correct"] is False, r["compared"]


def test_reference_is_plain_gradient_descent():
    """The benchmark's reference against a loop over one column at a
    time, for the logistic loss written as ``log(1 + e^{−y·Aw})``."""
    mod = find_module("matrices", "planted_logistic")
    prob = mod.build({"rows": 300, "cols": 20}, 5)
    a, y = prob.matrix, prob.meta["y"]
    assert set(np.unique(y)) == {-1.0, 1.0}
    rng = np.random.default_rng(1)
    w0 = rng.standard_normal((20, 3)) / np.sqrt(20)
    l2 = np.array([0.0, 1e-2, 1e-1])
    got = prob.reference(w0, l2, 0.5, 3)
    for j in range(3):
        w = w0[:, j].copy()
        for _ in range(3):
            s = 1.0 / (1.0 + np.exp(y * (a @ w)))        # σ(−y·Aw)
            w = w - 0.5 * ((a.T @ (-y * s)) / 300 + l2[j] * w)
        np.testing.assert_allclose(got[:, j], w - w0[:, j], rtol=1e-12,
                                   atol=1e-15)


def test_jobs_refuse_a_program_without_w0(monkeypatch):
    """Against a program whose RegressionJob takes no ``w0``/``l2`` the
    cell stops at once with an error (its parent cannot run it)."""
    import repro.cluster

    class OldRegressionJob:
        def __init__(self, a, y, strategy, epochs=5, loss="logistic",
                     lr=0.5, chunks=20, data=None):
            pass

    monkeypatch.setattr(repro.cluster, "RegressionJob", OldRegressionJob)
    jobs = find_module("jobs", "logreg_gd").Jobs
    with pytest.raises(RuntimeError, match="w0"):
        jobs(load_cell(CELL), None, None, None, 1)


def _round(cols, rpc, chunks, makespan):
    n = 12
    return RoundMetrics(
        round_id=0, strategy="GeneralS2C2", makespan=makespan,
        compute_time=makespan, decode_time=0.0,
        useful_rows=np.full(n, chunks * rpc / n),
        wasted_rows=np.zeros(n), speeds_measured=np.ones(n),
        planned_makespan=0.0, cols=cols, rows_per_chunk=rpc)


def _run(jobs, rounds, trace=None):
    return RunRecord(cell=None, jobs=jobs, rounds=rounds,
                     chunk_s=np.zeros(0),
                     geometry={"rows_per_chunk": 480, "cols": 5000,
                               "width": 1, "a_itemsize": 4},
                     peak=peaks("TPU v5 lite"), trace=trace)


def test_readers_read_a_synthetic_run():
    fwd, grad = _round(5000, 480, 200, 0.2), _round(96000, 25, 200, 0.3)
    job = JobMetrics(job_id=1, kind="regression", strategy="GeneralS2C2",
                     rounds=[fwd, grad, fwd, grad], gd_step_s=0.004,
                     gd_steps=2)
    run = _run([job], job.rounds, trace={"programs_s":
                                         {"jit_chunk_matvec": 0.5}})
    assert layer_reader("job.gd_step_ms")(run) == pytest.approx(2.0)
    assert layer_reader("engine.grad_round_ms")(run) == pytest.approx(300.0)
    want = 2 * 200 * (chunk_bytes(480, 5000, 1) + chunk_bytes(25, 96000, 1))
    assert layer_reader("kernel.gd_roofline")(run) == pytest.approx(
        want / 819e9 / 0.5 * 100)


@pytest.mark.parametrize("metric", READERS)
def test_readers_find_nothing_to_read(metric):
    """``None`` where there is nothing to read: no job or round, no
    trace, or a program whose records lack the fields."""
    read = layer_reader(metric)
    assert read(_run([], [])) is None
    old = _round(0, 0, 200, 0.2)
    job = JobMetrics(job_id=1, kind="matvec", strategy="GeneralS2C2",
                     rounds=[old])
    assert read(_run([job], [old], trace={"programs_s":
                                          {"jit_chunk_matvec": 0.5}})) is None
