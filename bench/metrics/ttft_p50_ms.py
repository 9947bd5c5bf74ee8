"""Median time to first token, from the scheduled arrival (ms)."""


def read(run):
    return run["window"]["ttft_p50_s"] * 1e3
