"""Mean trip count of the batched search loop per re-search (the program's
``research.trip`` counter: its slowest row's rounds)."""
from bench import program


def read(run):
    trip = program.counts(run, "research.trip")
    if trip is None:
        return None
    searched = [t for t in trip if t > 0]
    return sum(searched) / len(searched) if searched else None
