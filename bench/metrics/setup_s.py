"""Process start to the window's start: imports, weights, engine, warm-up
and any compilation (s)."""


def read(run):
    return run["setup_s"]
