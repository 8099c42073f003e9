"""Active users deployed at a compression level other than 0, over active
users, in percent: the mean over the measured ticks of the served plans.

Read from each tick's response (its ``comp`` levels and ``active`` slots),
the capture of the plan that outlives the run; the program counts the same
as ``install.compressed``.  None without a ladder (no response carries
``active``)."""
import numpy as np


def read(run):
    plans = {}
    for r in run.requests:
        resp = r.response
        if resp is not None and resp.get("active") is not None:
            plans.setdefault(resp["tick"], resp)
    shares = []
    for resp in plans.values():
        active = np.asarray(resp["active"], bool)
        if active.any():
            comp = np.asarray(resp["comp"])
            shares.append(100.0 * (comp[active] != 0).sum() / active.sum())
    return float(np.mean(shares)) if shares else None
