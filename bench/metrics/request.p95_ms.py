"""95th percentile of every request due in the window, from its due time to
its response (host clock), in a cell whose ticks are too uneven for it to
be held to a bound end to end: there a request's wait depends on which
tick its due time falls in, and so on the seed's arrivals.  An unanswered
request counts as never answered."""
import math

from bench import stats


def read(run):
    if not run.requests:
        return None
    lat = [(r.done - r.due) * 1e3 if r.done is not None else math.inf
           for r in run.requests]
    return stats.percentile(lat, 95)
