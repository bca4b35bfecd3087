"""From a profiler trace to the device's busy time, program times and breakdown.

Two steps, so that the second can be checked on a small recorded trace:

* :func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
  keeps plain lists: the events of each TPU device's ``XLA Ops`` and
  ``XLA Modules`` lines, and the benchmark's own ``bench.*`` host
  annotations;
* :func:`reduce` turns those into numbers.  The traced window is the
  ``bench.window`` annotation.  A device is busy while any of its ops
  runs (the union of the op intervals); its idle share is one minus busy
  over the window, averaged over the devices that ran anything.  A
  program's device time is the sum of its module events whose name starts
  with the program's name (``jit_chunk_matvec``), so every copy, pad and
  kernel that XLA put into that program counts.  The breakdown names the
  device ops that took most time, and the longest idle gaps by the host
  annotation that covered most of each gap.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10

Span = Tuple[float, float]


def extract(trace_dir: os.PathLike) -> Dict[str, Any]:
    """Plain event lists (ns) from the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    prof = ProfileData.from_file(files[-1])
    devices: Dict[str, Dict[str, List[list]]] = {}
    host: List[list] = []
    for plane in prof.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines.setdefault(line.name, []).extend(
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def union(spans: Iterable[Span]) -> List[Span]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Span] = []
    for a, b in sorted(spans):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(spans: Iterable[Span], lo: float, hi: float) -> List[Span]:
    return [(max(a, lo), min(b, hi)) for a, b in spans
            if b > lo and a < hi]


def _covered(spans: Sequence[Span]) -> float:
    return sum(b - a for a, b in spans)


def _gaps(busy: Sequence[Span], lo: float, hi: float) -> List[Span]:
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _label(gap: Span, host: Sequence[Tuple[str, float, float]]) -> str:
    """The host annotation that overlaps most of the gap."""
    best, best_t = "no bench annotation", 0.0
    per: Dict[str, float] = {}
    for name, a, b in host:
        o = min(b, gap[1]) - max(a, gap[0])
        if o > 0:
            per[name] = per.get(name, 0.0) + o
    for name, t in per.items():
        if t > best_t:
            best, best_t = name, t
    return best


def reduce(ex: Dict[str, Any], programs: Sequence[str] = ()) -> Dict[str, Any]:
    """Busy and window seconds, program seconds and the breakdown."""
    windows = [(s, s + d) for n, s, d in ex["host"] if n == "bench.window"]
    if not windows:
        raise ValueError("the trace holds no bench.window annotation")
    lo, hi = windows[0]
    host = [(n, s, s + d) for n, s, d in ex["host"]
            if n != "bench.window" and s + d > lo and s < hi]
    busy_total, used = 0.0, 0
    prog = {p: 0.0 for p in programs}
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[float, Span]] = []
    for lines in ex["devices"].values():
        ops = _clip([(s, s + d) for _, s, d in lines.get(OPS_LINE, [])],
                    lo, hi)
        if not ops:
            continue
        used += 1
        busy = union(ops)
        busy_total += _covered(busy)
        for name, s, d in lines.get(OPS_LINE, []):
            o = min(s + d, hi) - max(s, lo)
            if o > 0:
                op_time[name] = op_time.get(name, 0.0) + o
        for name, s, d in lines.get(MODULES_LINE, []):
            for p in programs:
                if name.startswith(p):
                    prog[p] += max(min(s + d, hi) - max(s, lo), 0.0)
        gaps.extend((g[1] - g[0], g) for g in _gaps(busy, lo, hi))
    if not used:
        raise ValueError("no device op ran inside the traced window")
    window_ns = hi - lo
    busy_ns = busy_total / used
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps, key=lambda g: -g[0])[:TOP]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "devices": used,
        "programs_s": {p: t * 1e-9 / used for p, t in prog.items()},
        "breakdown": {
            "device_ops": [[n, t * 1e-9] for n, t in top_ops],
            "idle_gaps": [[_label(g, host), t * 1e-9] for t, g in top_gaps],
        },
    }
