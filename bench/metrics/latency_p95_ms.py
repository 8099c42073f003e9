"""95th percentile of every request due in the window, from its due time to
its response (host clock); an unanswered request counts as never answered."""
import math

from bench import stats


def read(run):
    if not run.requests:
        return None
    lat = [(r.done - r.due) * 1e3 if r.done is not None else math.inf
           for r in run.requests]
    return stats.percentile(lat, 95)
