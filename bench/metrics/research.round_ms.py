"""Milliseconds of one batched search round, the final solve included: the
program's ``svc.research.engine`` span over the loop's trips, summed over
ticks."""
from bench import program


def read(run):
    ms = program.span_ms(run, "svc.research.engine")
    trip = program.counts(run, "research.trip")
    if ms is None or trip is None or not sum(trip):
        return None
    return sum(ms) / sum(trip)
