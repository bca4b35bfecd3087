"""Worker -> KernelBackend, inside the call: the time per ``compute_chunk``
call spent off the CPU, waiting on the device or for the interpreter
lock, in us.  The mean wall time of the window's calls
(``RoundMetrics.chunk_wall_s`` over ``chunk_calls``) less their mean
thread CPU time (``chunk_cpu_s`` over ``chunk_cpu_calls``), from the
program's per-chunk records (``obs.charge_start`` / ``charge_end``)."""


def read(run):
    calls = sum(getattr(r, "chunk_calls", 0) for r in run.rounds)
    cpu_calls = sum(getattr(r, "chunk_cpu_calls", 0) for r in run.rounds)
    if calls <= 0 or cpu_calls <= 0:
        return None
    wall = sum(r.chunk_wall_s for r in run.rounds) / calls
    cpu = sum(r.chunk_cpu_s for r in run.rounds) / cpu_calls
    return (wall - cpu) * 1e6
