"""95th percentile of the gaps between output tokens, pooled over every
request of the window (ms)."""


def read(run):
    return run["window"]["tpot_p95_s"] * 1e3
