"""Host milliseconds a step spends in the engine's own work: its spans
``engine.admit``, ``engine.schedule``, ``engine.dispatch`` and
``engine.emit`` (``ServeStats.phase_s``) over its steps, from the window's
start to the profiler's start (``run["stats_before_trace"]``), where the
profiler neither slows the host nor delays the snapshot. The device step
and the wait for it are not in it."""

HOST_PHASES = ("engine.admit", "engine.schedule", "engine.dispatch",
               "engine.emit")


def read(run):
    stats = run.get("stats_before_trace") or {}
    phase_s = stats.get("phase_s")
    if not phase_s or not any(p in phase_s for p in HOST_PHASES):
        return None
    n = stats.get("n_steps", 0)
    seconds = sum(phase_s.get(p, 0.0) for p in HOST_PHASES)
    return seconds / n * 1e3 if n > 0 else None
