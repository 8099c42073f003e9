"""Process start to the first measured tick: imports, the fleet's draw, the
bootstrap search, bucket warm-up and every compile on the way."""


def read(run):
    return run.setup_s if run.setup_s > 0 else None
