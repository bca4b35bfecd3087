"""JobService admission: mean queue wait (submit to slot start) of the
window's jobs, in ms, from ``JobMetrics.queue_wait``."""

import numpy as np


def read(run):
    waits = [j.queue_wait for j in run.jobs if np.isfinite(j.queue_wait)]
    if not waits:
        return None
    return float(np.mean(waits)) * 1e3
