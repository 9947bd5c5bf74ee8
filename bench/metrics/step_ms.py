"""Window length over the engine steps run in it (ms)."""


def read(run):
    n = run["steps"]["n"]
    return run["seconds"] / n * 1e3 if n else None
