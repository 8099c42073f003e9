"""Mean milliseconds per measured tick in the program's ``svc.install``
span: the re-searched plans published to the planner's cache."""
from bench import program


def read(run):
    ms = program.span_ms(run, "svc.install")
    return sum(ms) / len(ms) if ms else None
