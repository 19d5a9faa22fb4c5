"""Process start to the window's start: imports, weights, engine,
warm-up (compiles on a checkout's first run, cache loads after)."""


def compute(run):
    return run.setup_s
