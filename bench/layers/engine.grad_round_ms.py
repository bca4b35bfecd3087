"""CodedExecutionEngine, the gradient product: mean makespan of the
window's ``Aᵀ·r`` rounds, in ms.  A round is one of them where its
shard's width (``RoundMetrics.cols``) is not the configuration's
matrix's: it contracts over the matrix's rows."""


def read(run):
    cols = run.geometry["cols"]
    grad = [r.makespan for r in run.rounds
            if getattr(r, "cols", 0) not in (0, cols)]
    if not grad:
        return None
    return sum(grad) / len(grad) * 1e3
