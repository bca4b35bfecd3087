"""Worker -> KernelBackend dispatch: median wall time of one
``compute_chunk`` call in the window, in us, from the benchmark's timing
wrapper around the program's backend."""

import numpy as np


def read(run):
    if run.chunk_s.size == 0:
        return None
    return float(np.median(run.chunk_s)) * 1e6
