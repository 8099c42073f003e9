"""Deployed summed objective R (PAPER.md eq. 15) per cell, averaged over the
first ``cost_ticks`` measured ticks (the traffic file's K): the world's
trajectory is seeded, so parent and change price the same channels there."""


def read(run):
    k = int(run.cell.traffic["cost_ticks"])
    if len(run.ticks) < k:
        return None
    return sum(t.sum_R for t in run.ticks[:k]) / (k * run.cells_C)
