"""Seconds of the program's ``svc.bootstrap`` span: the cold search of every
cell when the service starts, part of set-up."""


def read(run):
    ms = (getattr(run, "setup_ms", None) or {}).get("svc.bootstrap")
    return ms / 1e3 if ms is not None else None
