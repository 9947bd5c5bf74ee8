"""Median wait from the scheduled arrival to admission into a slot (ms)."""


def read(run):
    return run["window"]["queue_wait_p50_s"] * 1e3
