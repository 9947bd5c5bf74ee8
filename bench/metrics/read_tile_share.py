"""Share of the mixed step's fixed-shape history-read tiles that hold a
real row (``ServeStats.read_tiles`` over ``read_tiles_held``), summed over
the steps from the window's start to the profiler's start
(``run["stats_before_trace"]``): the part of the read that is not waste."""


def read(run):
    stats = run.get("stats_before_trace") or {}
    held = stats.get("read_tiles_held")
    if not held or "read_tiles" not in stats:
        return None
    return stats["read_tiles"] / held
