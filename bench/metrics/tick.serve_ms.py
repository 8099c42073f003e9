"""Mean milliseconds per measured tick outside the four layer spans: plan
install, response build, queue drain and telemetry.  Absent where any of the
four spans is."""

LAYERS = ("tick.dynamics", "tick.reprice", "tick.drift", "tick.research")


def read(run):
    parts = [run.tick_spans(n) for n in LAYERS]
    if not run.ticks or any(p is None for p in parts):
        return None
    rest = [t.t1 - t.t0 - sum(p[k] for p in parts)
            for k, t in enumerate(run.ticks)]
    return 1e3 * sum(rest) / len(rest)
