"""Mean milliseconds per measured tick inside the ``tick.research`` span
(bench/spans/tick.research.json); absent where the span's target is gone."""


def read(run):
    per_tick = run.tick_spans("tick.research")
    if not per_tick:
        return None
    return 1e3 * sum(per_tick) / len(per_tick)
