"""Mean milliseconds per measured tick in the program's ``svc.respond``
span: the queue drained, the response built, every request resolved."""
from bench import program


def read(run):
    ms = program.span_ms(run, "svc.respond")
    return sum(ms) / len(ms) if ms else None
