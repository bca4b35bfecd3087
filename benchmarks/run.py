"""Benchmark runner: one module per paper figure/table.

Prints ``name,us_per_call,derived`` CSV and, when the cluster modules ran,
writes the machine-readable perf baseline ``BENCH_cluster.json`` (round
makespans, decode times, service jobs/s, and — from the throughput
module — work-stealing counters: per-inflight ``steals`` /
``retracted_chunks`` / ``pool_idle_frac`` plus the ``service/steal_ab``
pool-util A/B) next to the repo root so future PRs have a regression
trajectory.  Exits non-zero if any selected module raises, so CI fails
loudly instead of shipping a silently-empty baseline.  Usage:
    PYTHONPATH=src python -m benchmarks.run [--only fig8]
    PYTHONPATH=src python -m benchmarks.run --only cluster,throughput
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import traceback

from benchmarks.common import BENCH, Csv
from repro.jax_cache import use_compile_cache

MODULES = [
    ("fig1+3", "benchmarks.fig_overheads"),
    ("fig2", "benchmarks.fig_predictor"),
    ("fig6+7", "benchmarks.fig_controlled"),
    ("fig8-11", "benchmarks.fig_cloud"),
    ("fig12", "benchmarks.fig_polynomial"),
    ("cluster", "benchmarks.fig_cluster"),
    ("throughput", "benchmarks.fig_throughput"),
    ("kernels", "benchmarks.kernel_bench"),
    ("roofline", "benchmarks.roofline_bench"),
]

BENCH_PATH = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_cluster.json"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module tags/names to run")
    ap.add_argument("--bench-out", default=str(BENCH_PATH),
                    help="where to write the JSON perf baseline")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace-event JSON (Perfetto-"
                         "loadable) from one traced benchmark run")
    args = ap.parse_args()
    use_compile_cache()
    if args.trace_out:
        import benchmarks.common
        benchmarks.common.TRACE_OUT = args.trace_out
    only = set(args.only.split(",")) if args.only else None
    csv = Csv()
    print("name,us_per_call,derived")
    failures = 0
    for tag, modname in MODULES:
        if only is not None and not only & {tag, modname}:
            continue
        try:
            import importlib
            mod = importlib.import_module(modname)
            mod.main(csv)
        except Exception:
            traceback.print_exc()
            failures += 1
    if BENCH.data:
        out = pathlib.Path(args.bench_out)
        merged = {}
        if out.exists():        # partial (--only) runs refresh their slice
            try:
                merged = json.loads(out.read_text())
            except ValueError:
                merged = {}
        merged.update(BENCH.data)
        out.write_text(json.dumps(merged, indent=2, sort_keys=True) + "\n")
        print(f"# wrote {out} ({len(BENCH.data)} new / "
              f"{len(merged)} total entries)")
    print(f"# done, failures={failures}")
    return 1 if failures else 0


if __name__ == '__main__':
    sys.exit(main())
