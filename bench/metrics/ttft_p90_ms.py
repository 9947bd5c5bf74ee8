"""90th-percentile time to first token over all of the window's requests;
a request cut at the end of the drain counts as infinite (ms)."""


def read(run):
    return run["window"]["ttft_p90_s"] * 1e3
