"""Device self time under the engine's ``engine.score`` scope over device
busy time, in percent, in the traced tick (``device_scopes`` of the trace
reduction, bench/devscopes.py); absent where the run has no such
breakdown."""


def read(run):
    tr = run.trace or {}
    scopes = tr.get("device_scopes")
    if scopes is None or not tr.get("busy_s"):
        return None
    under = sum(s for path, s in scopes
                if "engine.score" in path.split("/"))
    return 100.0 * under / tr["busy_s"]
