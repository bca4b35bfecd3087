"""Worker -> KernelBackend, inside the call: the calling thread's CPU time
per ``compute_chunk`` call, in us.  Summed ``RoundMetrics.chunk_cpu_s``
over summed ``chunk_cpu_calls`` (the calls whose CPU the program read,
one in ``obs.CPU_SAMPLE`` of each thread's) of the window's rounds: the
program's own per-chunk records (``obs.charge_start`` / ``charge_end``), which leave out
the benchmark's straggler sleep around the call."""


def read(run):
    calls = sum(getattr(r, "chunk_cpu_calls", 0) for r in run.rounds)
    if calls <= 0:
        return None
    return sum(r.chunk_cpu_s for r in run.rounds) / calls * 1e6
