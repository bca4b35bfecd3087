#!/usr/bin/env python3
"""Label the device's longest idle gaps by the program's own ``s2c2.*`` spans.

Runs one cell of the on-chip benchmark with the profiler on, exactly as
``bench/run.py --trace 1`` does, and reads the profiler's ``.xplane.pb``
before the harness reduces and deletes it.  The benchmark's reduction keeps
only its own ``bench.*`` host annotations; this script reads the program's
spans on the same clock:

* ``spans`` — per span name, the count, total and mean seconds of the
  spans that start inside the traced window (``bench.window``); the
  benchmark's ``bench.straggler_sleep`` is read as one of them, since it
  sits inside the worker's chunk span;
* ``gaps`` — the longest device-idle gaps in the window (no op of the
  device's ``XLA Ops`` line runs).  Each host thread that carries
  ``s2c2.*`` spans is, at every instant, inside its innermost open span or
  in none (``-``).  A gap lists the thread-seconds of each innermost span
  inside it, on the worker threads (the only ones that give the device
  work) and on the others (round drivers, service slots), and is
  labelled by the workers' largest.

From the checkout root, on the chip::

    python3 scripts/gap_spans.py --workload mv65k-b1-strag2 --seed 7 \\
        --seconds 30 [--out <dir>]

The harness's result line is printed as ``bench/run.py`` prints it; the
spans and gaps go to standard error and, with ``--out``, to
``<out>/<workload>-<seed>.json``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from typing import Dict, List, Sequence, Tuple

T_PROCESS = time.perf_counter()

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: host events read: the program's spans, and the benchmark's straggler
#: sleep, which a benchmark backend adds inside the worker's chunk span
PREFIXES = ("s2c2.", "bench.straggler_sleep")
NONE = "-"
TOP_GAPS = 10

Event = Tuple[str, float, float]            # (name, start ns, end ns)


def innermost(events: Sequence[Event]) -> List[Event]:
    """Segments of one thread's timeline, each labelled by the innermost
    open span (spans of one thread nest; the one opened last is
    innermost), or :data:`NONE` between spans."""
    bounds = sorted({t for _, a, b in events for t in (a, b)})
    out: List[Event] = []
    for a, b in zip(bounds, bounds[1:]):
        mid = 0.5 * (a + b)
        open_ = [(s, name) for name, s, e in events if s <= mid < e]
        out.append((max(open_)[1] if open_ else NONE, a, b))
    return out


def _held(segs: Sequence[Sequence[Event]], lo: float,
          hi: float) -> List[List]:
    held: Dict[str, float] = {}
    for seg in segs:
        for name, a, b in seg:
            o = min(b, hi) - max(a, lo)
            if o > 0:
                held[name] = held.get(name, 0.0) + o
    return [[n, t * 1e-9] for n, t in sorted(held.items(),
                                               key=lambda kv: -kv[1])]


def label_gaps(gaps: Sequence[Tuple[float, float]],
               threads: Sequence[Sequence[Event]]) -> List[Dict]:
    """For each gap, the thread-seconds of each innermost span in it, on
    the worker threads (those with ``s2c2.worker.chunk`` spans: the only
    threads that give the device work) and on the others.  The gap's
    label is the workers' largest."""
    workers = [innermost(t) for t in threads
               if any(n == "s2c2.worker.chunk" for n, _, _ in t)]
    others = [innermost(t) for t in threads
              if not any(n == "s2c2.worker.chunk" for n, _, _ in t)]
    out = []
    for lo, hi in gaps:
        w = _held(workers, lo, hi)
        out.append({"gap_s": (hi - lo) * 1e-9,
                    "label": w[0][0] if w else NONE,
                    "workers": w[:4], "others": _held(others, lo, hi)[:3]})
    return out


def read_trace(trace_dir: str, top: int = TOP_GAPS) -> Dict:
    """``spans`` and ``gaps`` (see the module's docstring) of the newest
    trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    from bench import trace_reduce
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    prof = ProfileData.from_file(path)
    window = None
    ops: List[Tuple[float, float]] = []
    threads: List[List[Event]] = []
    for plane in prof.planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    ops += [(e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = []
                for e in line.events:
                    if e.name == "bench.window":
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith(PREFIXES):
                        evs.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns))
                if evs:
                    threads.append(evs)
    if window is None:
        raise ValueError("the trace holds no bench.window annotation")
    lo, hi = window
    spans: Dict[str, List[float]] = {}
    for evs in threads:
        for name, a, b in evs:
            if lo <= a < hi:
                spans.setdefault(name, []).append((b - a) * 1e-9)
    busy = trace_reduce.union(trace_reduce._clip(ops, lo, hi))
    gaps = sorted(trace_reduce._gaps(busy, lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    # only the spans that overlap a gap matter; a thread keeps one
    # worker.chunk span, so that it is still known as a worker's
    near = []
    for evs in threads:
        keep = [e for e in evs if any(e[1] < g1 and e[2] > g0
                                      for g0, g1 in gaps)]
        if any(n == "s2c2.worker.chunk" for n, _, _ in evs) and not any(
                n == "s2c2.worker.chunk" for n, _, _ in keep):
            keep.append(next(e for e in evs if e[0] == "s2c2.worker.chunk"))
        near.append(keep)
    return {"spans": {n: {"count": len(d), "total_s": sum(d),
                          "mean_s": sum(d) / len(d)}
                      for n, d in sorted(spans.items())},
            "gaps": label_gaps(gaps, [t for t in near if t])}


def main(argv=None) -> int:
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
    from bench import harness, trace_reduce
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    found: Dict = {}
    extract = trace_reduce.extract

    def extract_and_read(trace_dir):
        found.update(read_trace(str(trace_dir)))
        return extract(trace_dir)

    trace_reduce.extract = extract_and_read
    rc = harness.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds), "--trace", "1"],
                      t_process=T_PROCESS)
    line = json.dumps(found)
    print(f"gap_spans: {line}", file=sys.stderr, flush=True)
    if args.out and found:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, f"{args.workload}-{args.seed}.json"),
                  "w") as f:
            f.write(line)
    return rc


if __name__ == "__main__":
    sys.exit(main())
