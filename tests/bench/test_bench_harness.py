"""The on-chip benchmark's harness, checked on the CPU.

Covers the manifest against the benchmark's contract, discovery of every
file by name, a cell added by new files alone, percentiles over all jobs,
traffic made from the seed, the roofline arithmetic for both chunk shapes,
the trace reduction on a small recorded chip trace, the refusal to run
without a chip, and tiny cells run end to end in interpret mode.
"""

import gzip
import json
import math
import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

from bench_tiny import ROOT, run_tiny, tiny_root

from bench import data as bdata
from bench import roofline, stats, stragglers, trace_reduce
from bench.harness import JobRecord, end_to_end
from bench.manifest import find_module, layer_reader, load_cell, load_manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
RECORDED = ROOT / "bench" / "testdata" / "chip_trace_50ms.json.gz"

# a kind of matrix and a kind of job that the benchmark does not have,
# added by a file each
NEW_MATRIX = """
from bench import data as bdata


def build(config, seed):
    a = bdata.rng(seed, bdata.STREAM_MATRIX).uniform(
        -1.0, 1.0, (int(config["rows"]), int(config["cols"])))
    return bdata.Problem(a, lambda xs: a @ xs, {})
"""
NEW_JOBS = """
import numpy as np

from bench import data as bdata


class Jobs:
    \"\"\"One client sends the all-ones vector again and again.\"\"\"

    def __init__(self, cell, problem, data, strategy, seed):
        self.clients, self.width, self.products = 1, 1, 1
        self.problem, self.data, self.strategy = problem, data, strategy
        self.x = np.ones(problem.matrix.shape[1])

    def make(self, client, j):
        from repro.cluster import MatvecJob
        return MatvecJob(self.problem.matrix, [self.x], self.strategy,
                         chunks=self.data.chunks, batch=1,
                         data=self.data), [0]

    def check(self, done, seed):
        want = self.problem.reference(self.x[:, None])[:, 0]
        got = [np.asarray(r.handle.output).reshape(-1) for r in done]
        return {"checked": len(got),
                "max_rel_err": max(bdata.max_rel_err(g, want) for g in got),
                "median_rel_err": max(bdata.median_rel_err(g, want)
                                      for g in got)}
"""


@pytest.fixture(scope="module")
def manifest():
    return load_manifest()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench_root"))


class TestManifest:
    def test_keys_and_names(self, manifest):
        assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                                 "workloads", "end_to_end", "per_layer"}
        assert 1 <= manifest["run_seconds"] <= 51
        names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                     "per_layer") for x in manifest[k]]
        assert all(NAME.match(n) for n in names), names
        for k in ("configs", "workloads"):
            assert len({x["name"] for x in manifest[k]}) == len(manifest[k])
        metrics = manifest["end_to_end"] + manifest["per_layer"]
        assert len({m["name"] for m in metrics}) == len(metrics)
        assert all(UNIT.match(m["unit"]) for m in metrics)
        assert all(m["better"] in ("lower", "higher") for m in metrics)

    def test_bounds(self, manifest):
        e2e = {m["name"]: m for m in manifest["end_to_end"]}
        assert "setup_s" in e2e
        for m in e2e.values():
            assert 0.01 <= m["bound"] <= 0.25
            assert m["source"] in ("host_clock", "device_trace")

    def test_every_file_is_there(self, manifest):
        used = {w["config"] for w in manifest["workloads"]}
        assert used == {c["name"] for c in manifest["configs"]}
        for c in manifest["configs"]:
            assert any(c["file"].startswith(p + "/")
                       for p in manifest["paths"])
            assert (ROOT / c["file"]).is_file()
        for m in manifest["per_layer"]:
            assert callable(layer_reader(m["name"]))
            assert m["moves"] in {e["name"] for e in manifest["end_to_end"]}
        for w in manifest["workloads"]:
            assert w["chips"] in (1, 4)
            assert len(w["why"]) <= 200

    def test_command_stays_inside_paths(self, manifest):
        for word in manifest["command"][1:]:
            assert any(word.startswith(p + "/") for p in manifest["paths"])
            assert not word.startswith("/") and ".." not in word


class TestDiscovery:
    def test_cells_found_by_name(self, manifest):
        for w in manifest["workloads"]:
            cell = load_cell(w["name"])
            assert cell.config["name"] == w["config"]
            assert cell.mix["kind"] in ("controlled", "balanced")
            assert {m["name"] for m in cell.end_to_end} >= {
                "setup_s", "products_per_s", "job_p50_ms", "job_p95_ms"}
            assert cell.per_layer

    def test_unknown_cell_is_an_error(self):
        with pytest.raises(KeyError, match="no workload"):
            load_cell("no-such-cell")

    def test_new_cell_by_new_files_alone(self, root, tmp_path):
        """A configuration, traffic mix, straggler mix and per-layer metric
        are added as files plus a ``workloads`` entry; no file that is
        there changes."""
        before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
                  if p.is_file()}
        (root / "bench" / "mixes" / "strag1.json").write_text(json.dumps({
            "kind": "controlled", "n_stragglers": 1, "slowdown": 5.0,
            "variation": 0.2, "drift_sigma": 0.01, "trace_seed": 3,
            "iters": 1000}))
        traffic = json.loads(
            (root / "bench" / "traffic" / "tiny-b1.json").read_text())
        traffic.update(slowdown="strag1", clients=1)
        (root / "bench" / "traffic" / "tiny-strag1.json").write_text(
            json.dumps(traffic))
        (root / "bench" / "layers" / "service.jobs_done.py").write_text(
            "def read(run):\n    return len(run.jobs) or None\n")
        (root / "bench" / "matrices" / "dense_uniform.py").write_text(
            NEW_MATRIX)
        (root / "bench" / "jobs" / "matvec_ones.py").write_text(NEW_JOBS)
        cfg = json.loads(
            (root / "bench" / "configs" / "tiny-mv.json").read_text())
        cfg.update(name="tiny-uniform", matrix="dense_uniform")
        (root / "bench" / "configs" / "tiny-uniform.json").write_text(
            json.dumps(cfg))
        traffic.update(job="matvec_ones")
        (root / "bench" / "traffic" / "tiny-ones.json").write_text(
            json.dumps(traffic))
        man = json.loads((root / "BENCHMARK.json").read_text())
        man["configs"].append({"name": "tiny-uniform", "source": "test",
                               "file": "bench/configs/tiny-uniform.json",
                               "reduced": [], "why": "a new matrix kind"})
        man["workloads"].append({"name": "tiny-mv.strag1", "config": "tiny-mv",
                                 "traffic": "tiny-strag1", "chips": 1,
                                 "why": "added by files"})
        man["workloads"].append({"name": "tiny-uniform.ones",
                                 "config": "tiny-uniform",
                                 "traffic": "tiny-ones", "chips": 1,
                                 "why": "new matrix and job kinds by files"})
        man["per_layer"].append({
            "name": "service.jobs_done", "unit": "jobs", "better": "higher",
            "source": "program_counter", "layer": "JobService",
            "moves": "products_per_s", "workloads": ["tiny-mv.strag1"]})
        (root / "BENCHMARK.json").write_text(json.dumps(man))
        cell = load_cell("tiny-mv.strag1", root)
        assert cell.mix["n_stragglers"] == 1
        assert "service.jobs_done" in {m["name"] for m in cell.per_layer}
        assert "service.jobs_done" not in {
            m["name"] for m in load_cell("tiny-mv.b1", root).per_layer}
        result = run_tiny(root, "tiny-mv.strag1")
        assert result["correct"] is True
        result = run_tiny(root, "tiny-uniform.ones")
        assert result["correct"] is True, result["compared"]
        assert result["attempted"] >= 1
        for p, raw in before.items():
            assert p.read_bytes() == raw, p


class TestStats:
    @pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
    def test_percentile_matches_numpy(self, q):
        xs = np.random.default_rng(0).exponential(size=37)
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))

    def test_percentiles_over_all_jobs(self):
        """p95 is over every job done in the window, not a median of
        anything coarser."""
        lat = np.linspace(0.1, 1.0, 40)
        recs = [JobRecord(c, j, 2, None, None, 0.0, float(lat[c * 20 + j]))
                for c in range(2) for j in range(20)]
        out = end_to_end(recs, seconds=4.0, setup_s=1.5)
        assert out["products_per_s"] == 40 * 2 / 4.0
        assert out["job_p95_ms"] == pytest.approx(np.percentile(lat, 95) * 1e3)
        assert out["job_p50_ms"] == pytest.approx(np.median(lat) * 1e3)
        assert out["setup_s"] == 1.5


    def test_gc_pauses_are_recorded(self):
        import gc
        from bench.harness import GcPauses
        with GcPauses() as g:
            gc.collect()
        assert g.pauses[-1][0] == 2 and g.pauses[-1][1] >= 0.0
        assert "1 of generation 2" in g.summary()
        assert g._on_gc not in gc.callbacks


class TestTraffic:
    @pytest.mark.parametrize("seed", [0, 2**31 + 5, -3])
    def test_same_seed_same_inputs(self, seed):
        gen = find_module("matrices", "dense_gaussian").generate
        a = gen(64, 32, seed)
        assert np.array_equal(a, gen(64, 32, seed, threads=3))
        assert not np.array_equal(a, gen(64, 32, seed + 1))

    def test_workload_operands_follow_the_seed(self, root):
        cell = load_cell("tiny-mv.b16", root)

        def pools(seed):
            prob = cell.matrix_builder()(cell.config, seed)
            data = type("D", (), {"chunks": 4})()
            w = cell.jobs_class()(cell, prob, data, None, seed)
            return w.pool, [w.make(c, j)[1] for c in range(2) for j in range(3)]

        p1, idx1 = pools(11)
        p2, idx2 = pools(11)
        assert np.array_equal(p1, p2) and idx1 == idx2
        assert not np.array_equal(p1, pools(12)[0])
        assert all(len(i) == 4 for i in idx1)

    def test_strag2_mix_is_the_papers_controlled_cluster(self):
        mix = json.loads((ROOT / "bench" / "mixes" / "strag2.json").read_text())
        sp = stragglers.mix_speeds(mix, 12)
        assert sp.shape == (100000, 12)
        assert np.allclose(sp[0, -2:], 0.2, rtol=0.01)
        assert np.all((sp[0, :10] > 0.79) & (sp[0, :10] <= 1.01))
        assert np.isclose(sp[0, :10].max(), 1.0, rtol=0.01)
        assert np.array_equal(sp, stragglers.mix_speeds(mix, 12))

    @pytest.mark.parametrize("active,speeds,worker,ratio", [
        (True, [[1.0, 0.5, 0.2]], 0, 1.0),
        (True, [[1.0, 0.5, 0.2]], 1, 2.0),
        (True, [[1.0, 0.5, 0.2]], 2, 5.0),
        (False, [[1.0, 0.5, 0.2]], 2, 1.0),     # warm-up that compiles
        (True, None, 2, 1.0),                   # balanced
    ])
    def test_chunk_stretched_by_its_own_compute(self, active, speeds,
                                                worker, ratio):
        """A worker at mix speed ``s`` takes ``compute / s`` per chunk, and
        the program's worker is never told to sleep."""
        import time
        from bench.backends import TimedBackend

        class Slow:
            def compute_chunk(self, *args):
                time.sleep(0.02)
                return np.zeros(1)

        inj = stragglers.MixInjector(
            None if speeds is None else np.array(speeds))
        inj.active = active
        timed = TimedBackend(Slow(), inj)
        assert inj.speed(worker, 7) == stragglers.NO_SLEEP
        timed.compute_chunk(worker, "s", None, 0, 1, None)
        t0, t1, t2, w, asked = timed.records()[0]
        assert w == worker and t1 - t0 >= 0.02
        assert asked == pytest.approx((t1 - t0) * (ratio - 1.0))
        # the sleep takes at least what was asked; nothing when none was
        assert t2 - t1 >= asked if asked > 0 else t2 == t1
        assert timed.durations()[0] == pytest.approx(t1 - t0)


class TestRoofline:
    @pytest.mark.parametrize("rows,d,a_bytes,padded_x", [
        (328, 8192, 10_747_904, 4_194_304),      # mv: 65536 x 8192, (12, 10)
        (164, 32768, 21_495_808, 16_777_216),    # Kronecker scale 15
    ])
    def test_chunk_bytes(self, rows, d, a_bytes, padded_x):
        assert rows * d * 4 == a_bytes
        assert d * 128 * 4 == padded_x
        # required bytes: A, x unpadded, output; never the padded x
        assert roofline.chunk_bytes(rows, d, 1) == a_bytes + d * 4 + rows * 4
        assert roofline.chunk_bytes(rows, d, 16) == (a_bytes + d * 16 * 4
                                                     + rows * 16 * 4)
        assert roofline.chunk_flops(rows, d, 16) == 2 * rows * d * 16

    def test_share_and_bound(self):
        peak = roofline.peaks("TPU v5 lite")
        assert peak == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                        "hbm_bytes": 16e9}
        b = roofline.chunk_bytes(328, 8192, 1)
        share, bound = roofline.roofline_share(b, roofline.chunk_flops(
            328, 8192, 1), 2 * b / 819e9, peak)
        assert bound == "memory" and share == pytest.approx(0.5)
        share, bound = roofline.roofline_share(1.0, 197e12, 2.0, peak)
        assert bound == "compute" and share == pytest.approx(0.5)

    def test_unknown_device_is_an_error(self):
        with pytest.raises(KeyError, match="no peaks"):
            roofline.peaks("TPU v9 imaginary")


class TestTraceReduce:
    def test_synthetic(self):
        ex = {"devices": {"/device:TPU:0": {
            "XLA Ops": [["fusion", 10.0, 20.0], ["custom-call", 25.0, 15.0],
                        ["copy", 70.0, 10.0], ["outside", 200.0, 10.0]],
            "XLA Modules": [["jit_chunk_matvec(1)", 10.0, 30.0],
                            ["jit_other(2)", 70.0, 10.0]]}},
            "host": [["bench.window", 0.0, 100.0],
                     ["bench.compute_chunk", 42.0, 20.0],
                     ["bench.job", 0.0, 100.0]]}
        r = trace_reduce.reduce(ex, programs=("jit_chunk_matvec",))
        assert r["window_s"] == pytest.approx(100e-9)
        assert r["busy_s"] == pytest.approx(40e-9)       # [10,40] + [70,80]
        assert r["idle_share"] == pytest.approx(0.6)
        assert r["programs_s"]["jit_chunk_matvec"] == pytest.approx(30e-9)
        gaps = r["breakdown"]["idle_gaps"]
        assert gaps[0] == ["bench.job", pytest.approx(30e-9)]   # [40, 70]
        assert gaps[1][1] == pytest.approx(20e-9)
        assert [n for n, _ in r["breakdown"]["device_ops"]][:1] == ["fusion"]

    def test_window_is_required(self):
        with pytest.raises(ValueError, match="bench.window"):
            trace_reduce.reduce({"devices": {}, "host": []})

    def test_recorded_chip_trace(self):
        """50 ms of a traced window of ``mv65k-b1-strag2`` on one v5e."""
        with gzip.open(RECORDED, "rt") as f:
            ex = json.load(f)
        r = trace_reduce.reduce(ex, programs=("jit_chunk_matvec",))
        assert r["devices"] == 1
        assert r["window_s"] == pytest.approx(0.05, rel=1e-6)
        assert 0.0 < r["busy_s"] < r["window_s"]
        assert 0.0 < r["programs_s"]["jit_chunk_matvec"] <= r["window_s"]
        ops = r["breakdown"]["device_ops"]
        assert 0 < len(ops) <= 10 and all(t > 0 for _, t in ops)
        gaps = r["breakdown"]["idle_gaps"]
        assert 0 < len(gaps) <= 10
        assert all(math.isfinite(t) and t > 0 for _, t in gaps)
        assert sum(t for _, t in gaps) <= r["window_s"] - r["busy_s"] + 1e-12


def test_compiles_tells_cache_hits_from_compiles(tmp_path):
    """A program read back from the persistent cache is not a compile."""
    code = f"""
import sys
sys.path[:0] = [{str(ROOT)!r}]
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", {str(tmp_path)!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
from bench.compiles import Compiles
def scaled_sin(x):
    return jnp.sin(x) * 3.0 + 1.0
x = jnp.ones(8)
with Compiles() as first:
    jax.jit(scaled_sin)(x).block_until_ready()
jax.clear_caches()
with Compiles() as again:
    jax.jit(scaled_sin)(x).block_until_ready()
print(first.n, len(first.cached), again.n, len(again.cached), again.cached)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    n1, hit1, n2, hit2, names = proc.stdout.strip().split(" ", 4)
    assert int(n1) >= 1 and int(hit1) == 0
    assert int(n2) == 0 and int(hit2) >= 1
    assert "jit(scaled_sin)" in names


class TestNoChip:
    def _run(self, cwd):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run(
            [sys.executable, "bench/run.py", "--workload", "mv65k-b1-strag2",
             "--seed", "1", "--seconds", "1", "--trace", "0"],
            cwd=cwd, env=env, capture_output=True, text=True, timeout=120)

    def test_exits_nonzero_without_tpu(self):
        proc = self._run(ROOT)
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""
        assert "no TPU" in proc.stderr

    def test_exits_nonzero_with_only_the_benchmarks_files(self, tmp_path):
        import shutil
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        for p in load_manifest()["paths"]:
            shutil.copytree(ROOT / p, tmp_path / p,
                            ignore=shutil.ignore_patterns("__pycache__"))
        proc = self._run(tmp_path)
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""


@pytest.mark.parametrize("cell", ["tiny-mv.b1", "tiny-mv.b1-balanced",
                                  "tiny-mv.b16"])
def test_tiny_cell_runs_correct(root, cell):
    r = run_tiny(root, cell)
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "compared"
    c = r["compared"]["max_rel_err"]
    assert 0.0 < c["value"] <= c["limit"]
    assert r["metrics"]["setup_s"]["unit"] == "s"
