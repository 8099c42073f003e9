"""Programs lowered inside the measured window (JAX monitoring events):
anything here is a compile that set-up left for the window."""


def read(run):
    return run.compiles_in_window
