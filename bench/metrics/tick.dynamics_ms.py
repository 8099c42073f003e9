"""Mean milliseconds per measured tick inside the ``tick.dynamics`` span
(bench/spans/tick.dynamics.json); absent where the span's target is gone."""


def read(run):
    per_tick = run.tick_spans("tick.dynamics")
    if not per_tick:
        return None
    return 1e3 * sum(per_tick) / len(per_tick)
