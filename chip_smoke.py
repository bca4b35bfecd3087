#!/usr/bin/env python3
"""Smoke run of the coded-execution engine on a TPU chip.

Drives the engine's main path through the entry points a user calls:
``JobService`` → ``CodedExecutionEngine`` → ``Worker`` → ``KernelBackend``
→ the Pallas ``coded_matvec`` kernel, then the decode.  The data is held at
a size a deployment keeps on one chip: a dense 65536 × 8192 matrix, encoded
with the systematic Cauchy code at (n, k) = (12, 10) and 20 chunks, stays
device-resident as 12 float32 shards of 6560 × 8192 (about 2.6 GB).  Every
output is compared with the float64 host product ``A @ x``.

Phases (one process, one chip):

1. ``MatvecJob``s at B = 1 and one at ``batch=16`` under ``GeneralS2C2``
   and under ``MDSCoded``, two injected 5× stragglers, through one
   ``JobService``: a warm-up pass that compiles every shape, then the
   measured pass.
2. One round on a second engine with ``decode_with_kernel=True``, so the
   Pallas ``mds_decode`` runs on the chip too (after its own warm-up).

Run from the checkout root on a machine with a TPU::

    python chip_smoke.py [--seed N]

Without a TPU it exits non-zero before doing any work.  The last line of
standard output is one JSON object, ``{"ok": true, "device": {...}}``,
printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: max-norm error over max-norm of the float64 reference, every output
TOL = 1e-3

ROWS, COLS = 65536, 8192                 # the single-chip matrix
N, K, CHUNKS, STRAGGLERS = 12, 10, 20, 2
B1_VECTORS, BATCH = 4, 16
MIN_SHARD_BYTES = 2 * 10**9              # device-resident shards, at least


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def memory_line(device) -> str:
    """Device memory now and at its peak, as the backend reports them."""
    stats = device.memory_stats() or {}
    return (f"device {device.id} bytes_in_use {stats.get('bytes_in_use')} "
            f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


class Compiles:
    """Counts XLA backend compiles inside a ``with`` block (a persistent
    compile-cache hit is not one)."""

    def __enter__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class Checks:
    """Collects pass/fail per check so one run reports every failure."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, ok: bool, what: str) -> None:
        log(f"{'PASS' if ok else 'FAIL'} {what}")
        if not ok:
            self.failed.append(what)


def _run_jobs(svc, jobs, timeout: float):
    """Submit jobs together, wait for all; returns (handles, seconds)."""
    t0 = time.perf_counter()
    handles = [svc.submit(job) for job in jobs]
    for h in handles:
        if not h.wait(timeout):
            raise TimeoutError(f"job {h.metrics.job_id} unresolved after "
                               f"{timeout}s")
    return handles, time.perf_counter() - t0


def _check_outputs(check: Checks, tag: str, handles, refs) -> None:
    for h, ref in zip(handles, refs):
        m = h.metrics
        width = m.rounds[0].rhs_width if m.rounds else "?"
        name = f"{tag} {m.strategy} B={width}"
        check(m.error is None, f"{name}: job resolved without error "
                                f"({m.error})")
        if m.error is None:
            err = rel_err(h.output, ref)
            check(err <= TOL, f"{name}: max rel err {err!r} <= {TOL}")


def single_chip(rows: int, cols: int, seed: int, row_cost: float,
                check: Checks, min_shard_bytes: int,
                timeout: float = 600.0) -> None:
    """Phases 1 and 2 at a (rows, cols) matrix."""
    from repro.cluster import ClusterConfig, TraceInjector
    from repro.core.traces import controlled_traces

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    a = rng.standard_normal((rows, cols))
    xs = rng.standard_normal((B1_VECTORS + BATCH + 2, cols))
    ref = xs @ a.T                               # (vectors, rows) float64
    log(f"setup: data + float64 reference {time.perf_counter() - t0:.3f}s")
    cfg = ClusterConfig(n_workers=N, k=K, row_cost=row_cost,
                        starvation_timeout=120.0)
    injector = TraceInjector(controlled_traces(N, 1000,
                                               n_stragglers=STRAGGLERS,
                                               seed=seed))
    _phase_jobs(a, xs, ref, cfg, injector, check, min_shard_bytes, timeout)
    _phase_kernel_decode(a, xs, ref,
                         dataclasses.replace(cfg, decode_with_kernel=True),
                         injector, check, timeout)


def _phase_jobs(a, xs, ref, cfg, injector, check: Checks,
                min_shard_bytes: int, timeout: float) -> None:
    """B = 1 and B = 16 jobs under GeneralS2C2 and MDSCoded."""
    import jax
    from repro.cluster import (CodedExecutionEngine, JobService, MatvecJob,
                               kernel_backend)
    from repro.core.strategies import GeneralS2C2, MDSCoded

    rows = a.shape[0]
    strategies = [GeneralS2C2(N, K, rows, chunks=CHUNKS),
                  MDSCoded(N, K, rows)]
    backend = kernel_backend()
    eng = CodedExecutionEngine(cfg, injector, compute=backend)
    svc = JobService(eng, max_inflight=4)
    try:
        t0 = time.perf_counter()
        data = svc.share_matrix(a, chunks=CHUNKS)
        log(f"setup: encode + install {time.perf_counter() - t0:.3f}s "
            f"({N} shards of {data.partitions[0].shape})")
        batch = slice(B1_VECTORS, B1_VECTORS + BATCH)

        def jobs(b1: slice):
            out, refs = [], []
            for s in strategies:
                out += [MatvecJob(a, xs[b1], s, data=data),
                        MatvecJob(a, xs[batch], s, batch=BATCH, data=data)]
                refs += [ref[b1], ref[batch]]
            return out, refs

        warm, warm_refs = jobs(slice(0, 1))
        handles, secs = _run_jobs(svc, warm, timeout)
        log(f"warm-up (compile, shard upload, first rounds): {secs:.3f}s")
        _check_outputs(check, "warm-up", handles, warm_refs)

        measured, refs = jobs(slice(0, B1_VECTORS))
        with Compiles() as compiles:
            handles, secs = _run_jobs(svc, measured, timeout)
        log(f"measured pass: {len(measured)} jobs in {secs:.3f}s, "
            f"{compiles.n} backend compiles")
        for h in handles:
            for r in h.metrics.rounds:
                log(f"round {r.round_id} {r.strategy} B={r.rhs_width} "
                    f"makespan_s={r.makespan!r} decode_s={r.decode_time!r}")
        _check_outputs(check, "measured", handles, refs)

        info = backend.cache_info()
        log(f"device-resident shards: {info['shards']} totalling "
            f"{info['shard_bytes']} bytes")
        check(info["shard_bytes"] >= min_shard_bytes,
              f"shards on the device {info['shard_bytes']} >= "
              f"{min_shard_bytes} bytes")
        check(not eng.failed, f"engine.failed is empty ({eng.failed})")
        log(f"engine.dead (detector verdicts): {sorted(eng.dead)}")
        log(memory_line(jax.devices()[0]))
    finally:
        svc.close()
        eng.shutdown()


def _phase_kernel_decode(a, xs, ref, cfg, injector, check: Checks,
                         timeout: float) -> None:
    """One round decoded by the Pallas mds_decode kernel, after a warm-up."""
    import jax
    from repro.cluster import (CodedExecutionEngine, JobService, MatvecJob,
                               kernel_backend)
    from repro.core.strategies import GeneralS2C2

    eng = CodedExecutionEngine(cfg, injector, compute=kernel_backend())
    svc = JobService(eng, max_inflight=4)
    try:
        data = svc.share_matrix(a, chunks=CHUNKS)
        s = GeneralS2C2(N, K, a.shape[0], chunks=CHUNKS)
        tail = B1_VECTORS + BATCH
        for tag, i in (("kernel-decode warm-up", tail),
                       ("kernel-decode", tail + 1)):
            handles, secs = _run_jobs(
                svc, [MatvecJob(a, xs[i:i + 1], s, data=data)], timeout)
            r = handles[0].metrics.rounds
            log(f"{tag}: {secs:.3f}s" + (
                f" makespan_s={r[0].makespan!r} decode_s="
                f"{r[0].decode_time!r}" if r else ""))
            _check_outputs(check, tag, handles, [ref[i:i + 1]])
        check(not eng.failed, f"kernel-decode engine.failed is empty "
                              f"({eng.failed})")
        log(memory_line(jax.devices()[0]))
    finally:
        svc.close()
        eng.shutdown()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} backend={jax.default_backend()}")
    if jax.default_backend() != "tpu":
        print("chip_smoke: no TPU found; this smoke runs only on the chip",
              file=sys.stderr)
        return 2
    from repro.jax_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")

    check = Checks()
    t0 = time.perf_counter()
    single_chip(ROWS, COLS, args.seed, row_cost=1e-5, check=check,
                min_shard_bytes=MIN_SHARD_BYTES)
    log(f"total {time.perf_counter() - t0:.3f}s")
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": 1}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
