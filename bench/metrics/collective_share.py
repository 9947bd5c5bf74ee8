"""Share of the traced window that collective operations (all-gather,
all-reduce, ...) run on a device, averaged over the chips."""


def read(run):
    tr = run.get("trace")
    if not tr or run["chips"] < 2 or not tr.get("window_s"):
        return None
    return tr["collective_s"] / tr["window_s"]
