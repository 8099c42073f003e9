"""Mean milliseconds per measured tick inside the ``tick.drift`` span
(bench/spans/tick.drift.json); absent where the span's target is gone."""


def read(run):
    per_tick = run.tick_spans("tick.drift")
    if not per_tick:
        return None
    return 1e3 * sum(per_tick) / len(per_tick)
