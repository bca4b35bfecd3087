"""TPU v5e: share of the traced window in which no operation ran on the
device (one minus the union of op intervals over the window), in %."""


def read(run):
    if run.trace is None:
        return None
    return run.trace["idle_share"] * 100.0
