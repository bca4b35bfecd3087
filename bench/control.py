#!/usr/bin/env python3
"""Read the correctness numbers of a cell under the control or a fault.

The benchmark's own runs never run this.  It drives the same harness as
``bench/run.py``, on the chip, with the program's chunk product replaced
by the control (``--backend control``: the product one precision step
below the configuration's, see ``bench.backends``) or broken underneath
(``--backend fault:<name>``), for several seeds in one process, and
prints one JSON line per seed with
the numbers compared and their limits::

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5 \\
        --backend control
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench.backends import FaultyBackend, HighPrecisionControl  # noqa: E402
from bench.harness import run_cell  # noqa: E402
from bench.manifest import load_cell  # noqa: E402


def wrapper(backend: str):
    if backend == "control":
        return lambda inner: HighPrecisionControl()
    if backend.startswith("fault:"):
        fault = backend.split(":", 1)[1]
        return lambda inner: FaultyBackend(inner, fault)
    raise SystemExit(f"unknown --backend {backend!r}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--backend", default="control")
    args = ap.parse_args()
    cell = load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run_cell(cell, seed, args.seconds, False,
                     t_process=time.perf_counter(),
                     backend_wrap=wrapper(args.backend))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "backend": args.backend, "correct": r["correct"],
                          "attempted": r["attempted"],
                          "compared": r["compared"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
