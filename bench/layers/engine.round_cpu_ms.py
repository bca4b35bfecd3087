"""CodedExecutionEngine round driver: its own thread's CPU time per round,
plan start to decode end (``RoundMetrics.host_cpu_s``), in ms.  The mean
over the window's rounds that carry it: a coalesced follower's copy of a
round reads 0 and is the same round again."""


def read(run):
    cpu = [s for s in (getattr(r, "host_cpu_s", 0.0) for r in run.rounds)
           if s > 0.0]
    if not cpu:
        return None
    return sum(cpu) / len(cpu) * 1e3
