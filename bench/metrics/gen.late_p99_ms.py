"""99th percentile of how late the open-loop generator sent a request after
its due time (host clock): a starved generator is not a fast server."""
from bench import stats


def read(run):
    late = [(r.sent - r.due) * 1e3 for r in run.requests if r.sent is not None]
    return stats.percentile(late, 99)
