"""Chunk product on the device, both products of gradient descent:
required work of the window's rounds, ``A·w`` and ``Aᵀ·r`` each at its
own chunk shape (``RoundMetrics.rows_per_chunk`` and ``cols``), over the
``jit_chunk_matvec`` program's device time in the trace, as a share of
the roofline, in %.

The work is the rows each round reports computed (useful + wasted), so a
program that groups chunks into fewer calls is read against the same
work; bytes and FLOPs come from ``bench.roofline``.
"""

from bench.roofline import chunk_bytes, chunk_flops, roofline_share

PROGRAM = "jit_chunk_matvec"


def read(run):
    if run.trace is None or run.peak is None:
        return None
    seconds = run.trace["programs_s"].get(PROGRAM, 0.0)
    if seconds <= 0.0:
        return None
    total_bytes = total_flops = 0.0
    for r in run.rounds:
        rpc, cols = getattr(r, "rows_per_chunk", 0), getattr(r, "cols", 0)
        if rpc <= 0 or cols <= 0:
            return None
        width = r.rhs_width
        chunks = (r.total_useful + r.total_wasted) / (rpc * width)
        total_bytes += chunks * chunk_bytes(rpc, cols, width,
                                            run.geometry["a_itemsize"])
        total_flops += chunks * chunk_flops(rpc, cols, width)
    if total_bytes <= 0:
        return None
    share, _bound = roofline_share(total_bytes, total_flops, seconds, run.peak)
    return share * 100.0
