"""CodedExecutionEngine decode: mean ``RoundMetrics.decode_time`` of the
window's rounds, in ms."""

import numpy as np


def read(run):
    if not run.rounds:
        return None
    return float(np.mean([r.decode_time for r in run.rounds])) * 1e3
