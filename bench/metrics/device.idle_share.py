"""1 - (union of device-op intervals) / traced window, in percent, from the
profiler trace of one tick; absent where the run was not traced."""


def read(run):
    tr = run.trace or {}
    if not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
