"""Quickstart for the concurrent coded-execution engine (repro.cluster).

Spins up an in-process 10-worker cluster with a trace-driven straggler
injector, runs the same PageRank power iteration under GeneralS2C2 and the
(n, k)-MDS baseline on *real* worker threads (chunk-level any-k collection,
§4.3 timeout/reassign), shows one multi-RHS batched round doing the work
of 8 matvec rounds, then pushes a small heterogeneous job mix through the
multi-tenant JobService — with concurrent tenants coalescing onto a
shared matrix — and prints the service report.

Run:  PYTHONPATH=src python examples/cluster_demo.py
      PYTHONPATH=src python examples/cluster_demo.py --trace-out demo.json
      # then load demo.json in https://ui.perfetto.dev
"""

import argparse

import numpy as np

from repro.cluster import (ClusterConfig, CodedExecutionEngine, JobService,
                           MatvecJob, PageRankJob, RegressionJob,
                           TraceInjector, Tracer)
from repro.core.strategies import GeneralS2C2, MDSCoded
from repro.core.traces import controlled_traces

N_WORKERS, K, CHUNKS = 10, 8, 20
D = 2400


def make_stochastic(n: int, seed: int = 1) -> np.ndarray:
    rng = np.random.default_rng(seed)
    adj = (rng.random((n, n)) < 12.0 / n).astype(np.float64)
    col = adj.sum(0, keepdims=True)
    m = adj / np.maximum(col, 1)
    m[:, col[0] == 0] = 1.0 / n
    return m


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace-out", default=None,
                    help="export the whole demo as Chrome trace-event JSON "
                         "(load in Perfetto / chrome://tracing)")
    args = ap.parse_args()
    m = make_stochastic(D)
    traces = controlled_traces(N_WORKERS, 60, n_stragglers=2, seed=7)
    eng = CodedExecutionEngine(
        ClusterConfig(n_workers=N_WORKERS, k=K, row_cost=5e-5),
        injector=TraceInjector(traces),
        tracer=Tracer() if args.trace_out else None)
    try:
        data = eng.load_matrix(m, chunks=CHUNKS)
        r_ref = np.ones(D) / D
        for _ in range(15):
            r_ref = 0.15 / D + 0.85 * (m @ r_ref)

        print(f"{N_WORKERS}-worker engine, (n,k)=({N_WORKERS},{K}), "
              f"2 injected 5x stragglers")
        for name, strat in (
                ("general-s2c2", GeneralS2C2(N_WORKERS, K, D, chunks=CHUNKS)),
                ("mds-baseline", MDSCoded(N_WORKERS, K, D))):
            r = np.ones(D) / D
            ms, waves, wasted = [], 0, 0.0
            for _ in range(15):
                out = eng.matvec(data, r, strat)
                r = 0.15 / D + 0.85 * out.y[:D]
                ms.append(out.metrics.makespan)
                waves += out.metrics.reassign_waves
                wasted += out.metrics.total_wasted
            err = np.abs(r - r_ref).max() / r_ref.max()
            print(f"  [{name}] mean_iter={np.mean(ms[1:]) * 1e3:6.1f}ms "
                  f"reassign_waves={waves} wasted_rows={wasted:8.0f} "
                  f"pagerank_rel_err={err:.2e}")
            assert err < 1e-6

        # one multi-RHS batched round: 8 serving queries against the same
        # matrix as ONE (rows, 8) GEMM round instead of 8 GEMV rounds —
        # same coverage machinery, one set of dispatch/decode overheads
        import time
        rng = np.random.default_rng(0)
        xs = [rng.standard_normal(D) for _ in range(8)]
        t0 = time.perf_counter()
        for x in xs:
            eng.matvec(data, x, GeneralS2C2(N_WORKERS, K, D, chunks=CHUNKS))
        t_seq = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = eng.matmul(data, np.stack(xs, axis=1),
                         GeneralS2C2(N_WORKERS, K, D, chunks=CHUNKS))
        t_gemm = time.perf_counter() - t0
        assert np.allclose(out.y, m @ np.stack(xs, axis=1), atol=1e-8)
        print(f"\nbatched round: 8 matvec rounds {t_seq * 1e3:.0f}ms vs one "
              f"B=8 GEMM round {t_gemm * 1e3:.0f}ms "
              f"({t_seq / max(t_gemm, 1e-9):.1f}x)")

        # multi-tenant service: a burst of heterogeneous jobs; matvec
        # tenants share one matrix, so the coalescer merges their
        # concurrent rounds into multi-RHS batches
        svc = JobService(eng, max_queue=64, coalesce_hold_s=2e-3)
        try:
            a_shared = rng.standard_normal((480, 24))
            shared = svc.share_matrix(a_shared, chunks=8)
            # the shared-matrix tenants are admitted back-to-back so their
            # rounds overlap in the scheduler slots and can merge
            for i in range(8):
                svc.submit(MatvecJob(
                    a_shared, [rng.standard_normal(24) for _ in range(2)],
                    GeneralS2C2(N_WORKERS, K, 480, chunks=8),
                    chunks=8, data=shared))
            for i in range(16):
                strat = GeneralS2C2(N_WORKERS, K, 480, chunks=8)
                if i % 2 == 0:
                    svc.submit(PageRankJob(make_stochastic(480, seed=i),
                                           strat, iters=3, chunks=8))
                else:
                    a = rng.standard_normal((480, 12))
                    y = np.sign(a @ rng.standard_normal(12))
                    # both products of each step, A·w and Aᵀ·r, are coded
                    # rounds: the job's transpose is installed with it
                    svc.submit(RegressionJob(
                        a, y, strat, epochs=3, chunks=8, l2=1e-3,
                        w0=rng.standard_normal(12) / np.sqrt(12)))
            svc.drain(timeout=300)
            print("\nJobService report (24 heterogeneous jobs, shared-matrix "
                  "tenants coalesced):")
            print(svc.report().format())
        finally:
            svc.close()
    finally:
        eng.shutdown()
    if args.trace_out:
        n_events = eng.dump_trace(args.trace_out)
        print(f"\nwrote {args.trace_out} ({n_events} trace events) — "
              "load it in https://ui.perfetto.dev")
    print("OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
