"""What the program records of itself, as the metric readers take it.

``repro.fleet.service.telemetry.Telemetry`` keeps each tick's spans and
counters in ``Telemetry.last_tick`` (``TickSpans``: ``spans`` as ``(name,
start, end, parent)`` on the host clock, ``counters`` as name -> count).
A run that copies that record into each measured tick as
``Tick.telemetry``, and ``Telemetry.setup_ms`` into ``Run.setup_ms``, can
be read here; a run without them reads as absent (None), never as 0.
"""
from __future__ import annotations


def records(run) -> list | None:
    """Each measured tick's span record; None where any tick lacks one."""
    recs = [getattr(t, "telemetry", None) for t in run.ticks]
    if not recs or any(r is None for r in recs):
        return None
    return recs


def span_ms(run, name: str) -> list[float] | None:
    """Milliseconds in span ``name`` per measured tick; None where the ticks
    carry no record or no tick holds the span."""
    recs = records(run)
    if recs is None or not any(n == name for r in recs for n, *_ in r.spans):
        return None
    return [1e3 * sum(b - a for n, a, b, _ in r.spans if n == name)
            for r in recs]


def counts(run, name: str) -> list[int] | None:
    """What each measured tick added to counter ``name``; None where the
    ticks carry no record or no tick counted it."""
    recs = records(run)
    if recs is None or not any(name in r.counters for r in recs):
        return None
    return [r.counters.get(name, 0) for r in recs]
