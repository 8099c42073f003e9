"""Cell plans brought up to the current channel per second: C times the
completed ticks over the measured window (host clock)."""


def read(run):
    if not run.ticks or run.window_s <= 0:
        return None
    return run.cells_C * len(run.ticks) / run.window_s
