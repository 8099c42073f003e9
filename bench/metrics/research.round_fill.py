"""Row-rounds of the lockstep search loop that did work, in percent: the
real rows' rounds (``research.row_rounds``) over the real rows times the
loop's trip (``research.cells`` x ``research.trip``), summed over ticks."""
from bench import program


def read(run):
    rows = program.counts(run, "research.row_rounds")
    cells = program.counts(run, "research.cells")
    trip = program.counts(run, "research.trip")
    if rows is None or cells is None or trip is None:
        return None
    lockstep = sum(c * t for c, t in zip(cells, trip))
    return 100.0 * sum(rows) / lockstep if lockstep else None
