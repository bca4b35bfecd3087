"""JobService, the gradient-descent job's host step: mean span
``s2c2.job.gd_step`` per iteration (residual, then update; float64 on
the host) of the window's jobs, in ms, from ``JobMetrics.gd_step_s`` over
``JobMetrics.gd_steps``."""


def read(run):
    steps = sum(getattr(j, "gd_steps", 0) for j in run.jobs)
    if steps <= 0:
        return None
    return sum(j.gd_step_s for j in run.jobs) / steps * 1e3
