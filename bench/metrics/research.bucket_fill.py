"""Replanned cells over the rows of the replan buckets searched, in percent
(bucket padding is searched work that serves no cell)."""


def read(run):
    rows = sum(t.rows_searched for t in run.ticks)
    if rows == 0:
        return None
    return 100.0 * sum(len(t.replanned) for t in run.ticks) / rows
