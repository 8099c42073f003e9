"""Accepted compression-level moves over all accepted moves of the window's
re-searches, in percent, in the rows of real cells: each round spent on a
level is a round no reassignment gets.

Read from each tick's response, which carries its re-search's move counts
(the program's ``research.comp_moves`` and ``research.moves``); the run
keeps the responses, not the engine's traces.  None without a ladder or
without an accepted move."""


def read(run):
    moves = {}
    for r in run.requests:
        resp = r.response
        if resp is not None and resp.get("moves") is not None:
            moves.setdefault(resp["tick"], resp["moves"])
    total = sum(m["all"] for m in moves.values())
    if total == 0:
        return None
    return 100.0 * sum(m["comp"] for m in moves.values()) / total
