"""S2C2 allocation and section 4.3 waves: wasted rows over all rows
computed (useful + wasted), summed over the window's rounds
(``RoundMetrics``)."""


def read(run):
    useful = sum(r.total_useful for r in run.rounds)
    wasted = sum(r.total_wasted for r in run.rounds)
    if useful + wasted <= 0:
        return None
    return wasted / (useful + wasted)
