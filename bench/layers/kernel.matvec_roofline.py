"""Chunk product on the device: required work of the window's rounds over
the ``jit_chunk_matvec`` program's device time in the trace, as a share
of the roofline, in %.

The work is the rows the engine reports computed (useful + wasted, from
``RoundMetrics``), so a program that groups chunks into fewer calls is
read against the same work; bytes and FLOPs come from ``bench.roofline``.
"""

from bench.roofline import chunk_bytes, chunk_flops, roofline_share

PROGRAM = "jit_chunk_matvec"


def read(run):
    if run.trace is None or run.peak is None:
        return None
    seconds = run.trace["programs_s"].get(PROGRAM, 0.0)
    if seconds <= 0.0:
        return None
    g = run.geometry
    rows = sum(r.total_useful + r.total_wasted for r in run.rounds) / g["width"]
    chunks = rows / g["rows_per_chunk"]
    if chunks <= 0:
        return None
    total_bytes = chunks * chunk_bytes(g["rows_per_chunk"], g["cols"],
                                       g["width"], g["a_itemsize"])
    total_flops = chunks * chunk_flops(g["rows_per_chunk"], g["cols"],
                                       g["width"])
    share, _bound = roofline_share(total_bytes, total_flops, seconds, run.peak)
    return share * 100.0
