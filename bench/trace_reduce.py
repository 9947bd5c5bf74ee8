"""Reduce a JAX profiler trace (``*.xplane.pb``) to device metrics.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per device operation, named by its HLO instruction text (a collective
is known by its instruction name, the text before `` = ``). Busy time is the union of those intervals
inside the traced window; the window runs from the end of the host's
``start_trace`` event to the start of its ``stop_trace`` event (the whole
extent of the trace when those are missing). Idle gaps are the stretches of
the window on the first device with no operation running, each named by the
shortest host event that covers at least half of it (else the one that
overlaps it most), so a gap reads as what the host was doing meanwhile.
Host threads that carry a ``bench.monitor`` span (the harness's own side
thread) are left out of that naming.
"""
from __future__ import annotations

import collections
import glob
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

__all__ = ["find_trace", "reduce_trace", "union_ns", "COLLECTIVE_RE",
           "MONITOR_SPAN"]

COLLECTIVE_RE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|allgather|allreduce", re.I)
_OPS_LINES = ("XLA Ops",)
MONITOR_SPAN = "bench.monitor"


def find_trace(trace_dir) -> Optional[str]:
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def union_ns(intervals: List[Tuple[float, float]]) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals, lo, hi) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def _events(line):
    return [(ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def _load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def reduce_trace(path, *, kernel_patterns: Dict[str, str] = None,
                 top: int = 10) -> dict:
    """Busy and idle time, per-op totals, collective and kernel time.

    Returns ``window_s``; ``busy_s`` averaged over the devices; ``devices``;
    ``collective_s`` (collective ops, union per device, averaged);
    ``kernels``: for each name in ``kernel_patterns`` (a regex over op
    names) the summed device seconds and call count, averaged per device;
    ``device_ops``: the ``top`` ops by summed time on all devices (per
    device average); ``idle_gaps``: the ``top`` longest gaps on the first
    device, named by the host's overlapping event.
    """
    pd = _load(path)
    devices, host_events = [], []
    t_start = t_stop = None
    for plane in pd.planes:
        if re.match(r"/device:TPU:\d+$", plane.name):
            ops = []
            for line in plane.lines:
                if line.name in _OPS_LINES:
                    ops.extend(_events(line))
            devices.append((plane.name, ops))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = _events(line)
                for name, s, e in evs:
                    if name.endswith("start_trace"):
                        t_start = e
                    elif name.endswith("stop_trace"):
                        t_stop = s
                if not any(name == MONITOR_SPAN for name, _, _ in evs):
                    host_events.extend(evs)
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    all_ops = [x for _, ops in devices for x in ops]
    if not all_ops:
        return {"devices": 0}
    lo = t_start if t_start is not None else min(s for _, s, _ in all_ops)
    hi = t_stop if t_stop is not None else max(e for _, _, e in all_ops)
    if hi <= lo:
        lo, hi = (min(s for _, s, _ in all_ops), max(e for _, _, e in all_ops))
    n = len(devices)

    def clip(ops):
        return [(nm, max(s, lo), min(e, hi)) for nm, s, e in ops
                if e > lo and s < hi]

    busy, coll = 0.0, 0.0
    per_op = collections.defaultdict(float)
    kern = {k: [0.0, 0] for k in (kernel_patterns or {})}
    pats = {k: re.compile(v) for k, v in (kernel_patterns or {}).items()}
    for _, ops in devices:
        ops = clip(ops)
        busy += union_ns([(s, e) for _, s, e in ops])
        coll += union_ns([(s, e) for nm, s, e in ops
                          if COLLECTIVE_RE.search(nm.split(" = ", 1)[0])])
        for nm, s, e in ops:
            per_op[nm] += e - s
            for k, p in pats.items():
                if p.search(nm):
                    kern[k][0] += e - s
                    kern[k][1] += 1
    first = clip(devices[0][1])
    gaps = sorted(_gaps([(s, e) for _, s, e in first], lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    named = []
    for gs, ge in gaps:
        best, key = "none", None
        for nm, s, e in host_events:
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                # covering half the gap beats any partial overlap; among
                # those the innermost (shortest) event names the gap
                k = ((1, -(e - s)) if 2 * ov >= ge - gs else (0, ov))
                if key is None or k > key:
                    best, key = nm, k
        named.append([best, (ge - gs) * 1e-9])
    return {
        "devices": n,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / n * 1e-9,
        "collective_s": coll / n * 1e-9,
        "kernels": {k: {"seconds": v[0] / n * 1e-9, "calls": v[1] / n}
                    for k, v in kern.items()},
        "device_ops": [[nm[:160], t / n * 1e-9] for nm, t in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": named,
    }
