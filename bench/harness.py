"""Run one cell: set-up, warm-up, the measured window, the check, the result.

One process holds the chip and does everything in this order:

1. set-up — make the configuration's matrix on the host from the seed,
   encode and install it through ``JobService.share_matrix``, then warm
   up with the cell's own jobs: first with nobody sleeping, to compile
   every shape, then under the straggler mix (:mod:`bench.stragglers`),
   so that the engine's speed predictor has settled;
2. the window — closed-loop clients drive ``JobService.submit`` for the
   run's seconds; with ``trace`` on, the profiler records the window;
3. after the window closes and the device's peak memory is read, the
   program is shut down and the answers the clients received are compared
   with the float64 reference (the job kind's ``check``).

The end-to-end metrics are taken on the client side over the whole
window; the per-layer metrics are read by ``bench/layers/<metric>.py``.
"""

from __future__ import annotations

import dataclasses
import gc
import pathlib
import shutil
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import stats, stragglers
from bench.backends import TimedBackend
from bench.compiles import Compiles
from bench.manifest import ROOT, Cell, load_cell
from bench.roofline import peaks

#: seconds a client waits for one job before counting it as never returned
JOB_TIMEOUT_S = 120.0
TRACE_DIR = ".bench_trace"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_chips(chips: int):
    import jax
    if jax.default_backend() != "tpu":
        raise NoChip(f"no TPU: JAX's default backend is "
                     f"{jax.default_backend()!r}")
    devs = jax.devices()
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs


class GcPauses:
    """The interpreter's garbage-collector pauses inside a ``with`` block.

    A collection holds the interpreter lock, so every worker thread and
    the device wait through it.
    """

    def __enter__(self):
        self.pauses: List[tuple] = []      # (generation, seconds)
        self._start = 0.0
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._start))

    def summary(self) -> str:
        secs = [t for _, t in self.pauses]
        return (f"{len(secs)} collections "
                f"({sum(g == 2 for g, _ in self.pauses)} of generation 2), "
                f"longest {max(secs, default=0.0) * 1e3:.3f} ms, "
                f"total {sum(secs) * 1e3:.3f} ms")


@dataclasses.dataclass
class JobRecord:
    client: int
    index: int
    products: int
    handle: Any
    operands: Any               # pool indices of the job's vectors, or None
    t_submit: float
    t_return: float = float("nan")

    @property
    def latency(self) -> float:
        return self.t_return - self.t_submit


@dataclasses.dataclass
class RunRecord:
    """What a per-layer reader sees of one run."""

    cell: Cell
    jobs: List[Any]              # JobMetrics of the jobs done in the window
    rounds: List[Any]            # RoundMetrics of those jobs
    chunk_s: np.ndarray          # wall seconds of chunk calls in the window
    geometry: Dict[str, int]
    peak: Optional[Dict[str, float]]
    trace: Optional[Dict[str, Any]] = None


def run_clients(svc, work, *, jobs_each: Optional[int] = None,
                until: Optional[float] = None, start_index: int = 0,
                annotate: bool = False) -> List[JobRecord]:
    """Closed loop: each client submits its next job when the last returns.

    Stops after ``jobs_each`` jobs per client, or, with ``until``, submits
    no job after that ``perf_counter`` time and waits for those in flight.
    """
    out: List[JobRecord] = []
    lock = threading.Lock()

    def client(c: int) -> None:
        j = start_index
        while True:
            if jobs_each is not None and j - start_index >= jobs_each:
                return
            if until is not None and time.perf_counter() >= until:
                return
            job, idx = work.make(c, j)
            rec = JobRecord(c, j, work.products, None, idx,
                            time.perf_counter())
            if annotate:
                import jax.profiler
                with jax.profiler.TraceAnnotation("bench.job"):
                    rec.handle = svc.submit(job)
                    ok = rec.handle.wait(JOB_TIMEOUT_S)
            else:
                rec.handle = svc.submit(job)
                ok = rec.handle.wait(JOB_TIMEOUT_S)
            if ok:
                rec.t_return = time.perf_counter()
            with lock:
                out.append(rec)
            if not ok:
                return
            j += 1

    threads = [threading.Thread(target=client, args=(c,), name=f"client-{c}")
               for c in range(work.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sorted(out, key=lambda r: (r.client, r.index))


def _job_ok(rec: JobRecord) -> bool:
    return (np.isfinite(rec.t_return) and rec.handle is not None
            and rec.handle.metrics.error is None
            and rec.handle.output is not None)


def _calibration_report(timed: TimedBackend, inj, t_open: float,
                        t_close: float) -> Dict[str, float]:
    """How the emulation came out in the window: the share of its chunk
    time that the speed-1 worker, and all non-stragglers, spent asleep,
    as asked of ``time.sleep`` and as taken (which adds the wait for the
    interpreter lock after it), and the stragglers' chunk rate over the
    other workers' (the mix asks 1/5)."""
    calls = timed.records(t_open, t_close)
    if inj.speeds is None or calls.shape[0] == 0:
        return {}
    speed0 = inj.speeds[0]
    worker = calls[:, 3].astype(int)
    compute = calls[:, 1] - calls[:, 0]
    chunk = calls[:, 2] - calls[:, 0]
    asked = calls[:, 4]
    fast = int(np.argmax(speed0))
    slow = speed0[worker] < 0.5
    out: Dict[str, float] = {"speed1_worker": fast}
    for name, keep in (("speed1", worker == fast), ("nonstraggler", ~slow)):
        out[f"{name}_sleep_share_asked"] = float(
            asked[keep].sum() / (compute[keep] + asked[keep]).sum())
        out[f"{name}_sleep_share_taken"] = float(
            (chunk[keep] - compute[keep]).sum() / chunk[keep].sum())
    out["straggler_rate_ratio"] = float(chunk[~slow].mean()
                                        / chunk[slow].mean())
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, backend_wrap: Optional[Callable] = None,
             require_chip: bool = True) -> Dict[str, Any]:
    """One run of ``cell``; returns the result line as a dict."""
    import jax
    devs = require_chips(cell.chips) if require_chip else jax.devices()
    from repro.cluster import ClusterConfig, CodedExecutionEngine, JobService
    from repro.cluster import kernel_backend
    from repro.core.strategies import GeneralS2C2
    from repro.jax_cache import use_compile_cache
    if require_chip:
        log(f"compile cache: {use_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = devs[0]
    peak = peaks(dev.device_kind) if require_chip else None

    cfg, tr = cell.config, cell.traffic
    code = cfg["code"]
    n, k, chunks = int(code["n"]), int(code["k"]), int(code["chunks"])
    t0 = time.perf_counter()
    log(f"setup: {t0 - t_process:.3f}s from process start to the chip")
    problem = cell.matrix_builder()(cfg, seed)
    log(f"setup: {cfg['matrix']} matrix {problem.matrix.shape} "
        f"{time.perf_counter() - t0:.3f}s")
    inner = kernel_backend()
    if backend_wrap is not None:
        inner = backend_wrap(inner)
    inj = stragglers.MixInjector(stragglers.mix_speeds(cell.mix, n))
    timed = TimedBackend(inner, inj)
    eng = CodedExecutionEngine(
        ClusterConfig(n_workers=n, k=k, row_cost=float(tr["row_cost"])),
        inj, compute=timed)
    svc = JobService(eng, max_inflight=int(tr["max_inflight"]),
                     coalesce=bool(tr["coalesce"]))
    try:
        with Compiles() as setup_compiles:
            t0 = time.perf_counter()
            data = svc.share_matrix(problem.matrix, chunks=chunks)
            log(f"setup: encode + install {time.perf_counter() - t0:.3f}s "
                f"({n} shards of {data.partitions[0].shape})")
            strategy = GeneralS2C2(n, k, problem.matrix.shape[0],
                                   chunks=chunks)
            work = cell.jobs_class()(cell, problem, data, strategy, seed)
            t0 = time.perf_counter()
            recs = run_clients(svc, work, jobs_each=int(tr["compile_jobs"]))
            index = int(tr["compile_jobs"])
            inj.active = True
            recs += run_clients(svc, work, jobs_each=int(tr["settle_jobs"]),
                                start_index=index)
            index += int(tr["settle_jobs"])
        bad = [r for r in recs if not _job_ok(r)]
        if bad:
            raise RuntimeError(f"{len(bad)} warm-up job(s) failed: "
                               f"{bad[0].handle.metrics.error if bad[0].handle else 'no handle'}")
        log(f"setup: warm-up {time.perf_counter() - t0:.3f}s; encode, "
            f"install and warm-up made {setup_compiles.summary()}")

        trace_root = None
        if trace:
            trace_root = cell.root / TRACE_DIR / cell.name
            shutil.rmtree(trace_root, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_root), profiler_options=opts)
            timed.annotate = True
        with Compiles() as window_compiles, GcPauses() as gc_pauses:
            t_open = time.perf_counter()
            setup_s = t_open - t_process
            t_close = t_open + seconds
            if trace:
                # the annotation spans exactly the window; the clients'
                # jobs in flight at its close finish outside it
                out: List[List[JobRecord]] = []
                clients = threading.Thread(target=lambda: out.append(
                    run_clients(svc, work, until=t_close, start_index=index,
                                annotate=True)))
                with jax.profiler.TraceAnnotation("bench.window"):
                    clients.start()
                    time.sleep(max(t_close - time.perf_counter(), 0.0))
                clients.join()
                window = out[0]
            else:
                window = run_clients(svc, work, until=t_close,
                                     start_index=index)
        if trace:
            jax.profiler.stop_trace()
            timed.annotate = False
        log(f"window: {len(window)} jobs submitted, "
            f"{window_compiles.n} backend compiles inside the window; "
            f"garbage collector: {gc_pauses.summary()}")
        cal = _calibration_report(timed, inj, t_open, t_close)
        log("calibration: " + " ".join(f"{k_}={v!r}" for k_, v in
                                        cal.items()))
        stats_ = dev.memory_stats() or {}
        mem_peak = stats_.get("peak_bytes_in_use")
        in_window = [r for r in window if _job_ok(r) and r.t_return <= t_close]
        record = RunRecord(
            cell=cell, jobs=[r.handle.metrics for r in in_window],
            rounds=[m for r in in_window for m in r.handle.metrics.rounds],
            chunk_s=timed.durations(t_open, t_close),
            geometry={"rows_per_chunk": -(-problem.matrix.shape[0]
                                          // (k * chunks)),
                      "cols": problem.matrix.shape[1],
                      "width": work.width, "a_itemsize": 4},
            peak=peak)
        if trace_root is not None:
            from bench import trace_reduce
            t0 = time.perf_counter()
            record.trace = trace_reduce.reduce(
                trace_reduce.extract(trace_root),
                programs=("jit_chunk_matvec",))
            shutil.rmtree(trace_root, ignore_errors=True)
            log(f"trace: reduced in {time.perf_counter() - t0:.3f}s")
    finally:
        svc.close()
        eng.shutdown()
    failed = sum(1 for r in window if not _job_ok(r))
    t0 = time.perf_counter()
    check = work.check([r for r in window if _job_ok(r)], seed)
    compared = {name: {"value": check[name], "limit": float(lim)}
                for name, lim in cfg["limits"].items()}
    compared["jobs_failed"] = {"value": failed, "limit": 0}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in compared.values())
    log(f"check: {check['checked']} answers against the float64 reference "
        f"in {time.perf_counter() - t0:.3f}s")

    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": len(window), "failed": failed}
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for m in cell.per_layer:
            v = cell.reader(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e = end_to_end(in_window, seconds, setup_s)
        for m in cell.end_to_end:
            if np.isfinite(e2e.get(m["name"], np.nan)):
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    result["metrics"] = metrics
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    if record.trace is not None:
        device["busy_s"] = record.trace["busy_s"]
        device["window_s"] = record.trace["window_s"]
        result["breakdown"] = record.trace["breakdown"]
    result["device"] = device
    result["compared"] = compared
    return result


def end_to_end(in_window: List[JobRecord], seconds: float,
               setup_s: float) -> Dict[str, float]:
    lat = [r.latency for r in in_window]
    return {"setup_s": setup_s,
            "products_per_s": sum(r.products for r in in_window) / seconds,
            "job_p50_ms": stats.percentile(lat, 50) * 1e3,
            "job_p95_ms": stats.percentile(lat, 95) * 1e3}


def main(argv: Optional[List[str]] = None, *, t_process: float,
         root: pathlib.Path = ROOT) -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, root)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_process=t_process)
    except NoChip as exc:
        log(f"bench: {exc}; this benchmark runs only on the chip")
        return 3
    for name, c in result["compared"].items():
        log(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
