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

Named scopes (``scope_seconds``): device seconds under each
``jax.named_scope`` a cell lists, from the framework op name that ``xprof``
gives each operation. Idle by span (``idle_by_span``): the first device's
idle seconds in the window, split by the innermost ``engine.*`` host span
the host was in.
"""
from __future__ import annotations

import collections
import glob
import json
import re
import shutil
import sys
import tempfile
import traceback
from pathlib import Path
from typing import Dict, List, Optional, Tuple

__all__ = ["find_trace", "reduce_trace", "union_ns", "scope_seconds",
           "inherited_scopes", "idle_by_span", "COLLECTIVE_RE", "MONITOR_SPAN"]

COLLECTIVE_RE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|allgather|allreduce", re.I)
_OPS_LINES = ("XLA Ops",)
_DEVICE_RE = re.compile(r"/device:TPU:\d+$")
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


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message's fields: an int for a
    varint, a memoryview for a length-delimited field, None otherwise."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind, v = key & 7, None
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, v


def _hlo_graphs(path) -> dict:
    """{program id: {instruction: (framework op name, operand names)}} from
    the HLO modules a TPU trace keeps in its ``/host:metadata`` plane
    (XSpace.planes 1; XPlane.name 2, .event_metadata 4; XEventMetadata.id
    1, .stats 5; XStat.bytes_value 6; HloProto.hlo_module 1;
    HloModuleProto.computations 3; HloComputationProto.instructions 2;
    HloInstructionProto.name 1, .metadata 7 (OpMetadata.op_name 2), .id 35,
    .operand_ids 36)."""
    graphs = {}
    for f, plane in _fields(memoryview(Path(path).read_bytes())):
        fields = list(_fields(plane)) if f == 1 else []
        if bytes(next((v for n, v in fields if n == 2), b"")) != (
                b"/host:metadata"):
            continue
        for entry in (v for n, v in fields if n == 4):
            md = next(v for n, v in _fields(entry) if n == 2)
            mid = next((v for n, v in _fields(md) if n == 1), None)
            for stat in (v for n, v in _fields(md) if n == 5):
                for n, hlo in _fields(stat):
                    if n == 6:
                        graphs[mid] = _instructions(hlo)
    return graphs


def _instructions(hlo) -> dict:
    names, ops = {}, []
    module = next(v for n, v in _fields(hlo) if n == 1)
    for comp in (v for n, v in _fields(module) if n == 3):
        for ins in (v for n, v in _fields(comp) if n == 2):
            name, op_name, iid, operands = "", "", None, []
            for n, v in _fields(ins):
                if n == 1:
                    name = bytes(v).decode()
                elif n == 7:
                    op_name = next((bytes(x).decode() for k, x in _fields(v)
                                    if k == 2), "")
                elif n == 35:
                    iid = v
                elif n == 36:
                    if isinstance(v, int):
                        operands.append(v)
                    else:
                        j = 0
                        while j < len(v):
                            o, j = _varint(v, j)
                            operands.append(o)
            names[iid] = name
            ops.append((name, op_name, operands))
    return {name: (op_name, [names.get(o, "") for o in operands])
            for name, op_name, operands in ops}


def _scopes_of(op_name: str, scopes) -> frozenset:
    parts = op_name.rstrip(":").split("/")
    return frozenset(s for s in scopes if s in parts)


def _users(graph: dict) -> dict:
    users = {}
    for n, (_, operands) in graph.items():
        for o in operands:
            users.setdefault(o, []).append(n)
    return users


def inherited_scopes(graph: dict, name: str, scopes,
                     users: Optional[dict] = None) -> frozenset:
    """Scopes of an instruction the compiler made without a framework op
    name (a layout copy): those shared by the nearest named instructions on
    both sides of it (through other unnamed ones), its producers' and its
    users'; a side with none found is left out. ``users`` is
    ``_users(graph)``, passed in where one graph is asked many times."""
    if users is None:
        users = _users(graph)
    sides = []
    for step in (lambda n: graph.get(n, ("", []))[1],
                 lambda n: users.get(n, [])):
        seen, todo, found = {name}, [name], []
        while todo:
            for n in step(todo.pop()):
                if n in seen or n not in graph:
                    continue
                seen.add(n)
                if graph[n][0]:
                    found.append(_scopes_of(graph[n][0], scopes))
                else:
                    todo.append(n)
        if found:
            sides.append(frozenset.intersection(*found))
    return frozenset.intersection(*sides) if sides else frozenset()


def scope_seconds(path, scopes, *, n_dev=None):
    """Device seconds a chip of the ops whose framework op name has each
    scope as a path component (``jit(f)/paged_attention/dot_general``),
    from ``xprof``'s ``hlo_stats`` self times, an op the compiler made
    without such a name counted where ``inherited_scopes`` puts it; ``None``
    for a trace with no device op, or where ``xprof`` is missing. ``all`` is
    every op's self time, the scopes' upper bound; ``unnamed`` is the part
    with no name of its own that no scope took. ``hlo_stats`` writes a cache
    beside the trace it reads, so it reads a copy."""
    try:
        from xprof.convert.raw_to_tool_data import xspace_to_tool_data
    except ImportError:
        return None
    if n_dev is None:
        n_dev = sum(1 for p in _load(path).planes
                    if _DEVICE_RE.match(p.name))
    if not n_dev:
        return None
    with tempfile.TemporaryDirectory() as tmp:
        copy = Path(tmp) / "trace.xplane.pb"
        shutil.copy(path, copy)
        raw, _ = xspace_to_tool_data([str(copy)], "hlo_stats", {})
    table = json.loads(raw)
    cols = [c["id"] for c in table["cols"]]
    out = dict.fromkeys(scopes, 0.0)
    out["all"] = out["unnamed"] = 0.0
    graphs, users = None, {}
    for row in table["rows"]:
        cell = dict(zip(cols, (c.get("v") for c in row["c"])))
        us = float(cell.get("total_self_time") or 0.0)
        out["all"] += us
        tf_op = str(cell.get("tf_op_name") or "")
        if tf_op:
            got = _scopes_of(tf_op, scopes)
        else:
            if graphs is None:
                graphs = _hlo_graphs(path)
            pid = int(cell["program_id"])
            graph = graphs.get(pid, {})
            if pid not in users:
                users[pid] = _users(graph)
            got = inherited_scopes(graph, str(cell["hlo_op_name"]), scopes,
                                   users[pid])
            if not got:
                out["unnamed"] += us
        for s in got:
            out[s] += us
    return {k: v * 1e-6 / n_dev for k, v in out.items()}


def _innermost(spans):
    """Nested (start, end, name) spans -> disjoint (start, end, name)
    pieces, each named by the innermost span covering it."""
    out, stack, t = [], [], None
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, nm = stack.pop()
            if end > t:
                out.append((t, end, nm))
                t = end
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        t = s
        stack.append((e, name))
    while stack:
        end, nm = stack.pop()
        if end > t:
            out.append((t, end, nm))
            t = end
    return out


def idle_by_span(path, prefix: str = "engine."):
    """Idle seconds of the first device in the traced window (start_trace
    to stop_trace, as ``reduce_trace``), split by the innermost host span
    whose name starts with ``prefix``; ``none`` is idle outside them all.
    ``None`` for a trace with no device op."""
    return _idle_by_span(_load(path), prefix)


def _idle_by_span(pd, prefix: str = "engine."):
    devices, spans = [], []
    lo = hi = None
    for plane in pd.planes:
        if _DEVICE_RE.match(plane.name):
            ops = [x for line in plane.lines if line.name in _OPS_LINES
                   for x in _events(line)]
            devices.append((int(plane.name.rsplit(":", 1)[1]), ops))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name.endswith("start_trace"):
                        lo = e
                    elif name.endswith("stop_trace"):
                        hi = s
                    elif name.startswith(prefix):
                        spans.append((s, e, name))
    devices = [ops for _, ops in sorted(devices) if ops]
    if not devices:
        return None
    ops = devices[0]
    if lo is None or hi is None or hi <= lo:
        lo, hi = min(s for _, s, _ in ops), max(e for _, _, e in ops)
    gaps = _gaps([(max(s, lo), min(e, hi)) for _, s, e in ops
                  if e > lo and s < hi], lo, hi)
    out = {"none": sum(e - s for s, e in gaps)}
    pieces = _innermost(spans)
    i = 0
    for gs, ge in gaps:
        while i < len(pieces) and pieces[i][1] <= gs:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < ge:
            ov = min(ge, pieces[j][1]) - max(gs, pieces[j][0])
            if ov > 0:
                out[pieces[j][2]] = out.get(pieces[j][2], 0.0) + ov
                out["none"] -= ov
            j += 1
    return {k: v * 1e-9 for k, v in out.items()}


def _reading(fn, *args, **kw):
    """``fn``'s reading, or ``None`` where it fails: the failure is printed,
    never raised, since a reading left out does not fail the run."""
    try:
        return fn(*args, **kw)
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return None


def reduce_trace(path, *, kernel_patterns: Dict[str, str] = None,
                 scopes=None, top: int = 10) -> dict:
    """Busy and idle time, per-op totals, collective and kernel time, named
    scopes and idle time by host span.

    Returns ``window_s``; ``busy_s`` averaged over the devices; ``devices``;
    ``collective_s`` (collective ops, union per device, averaged);
    ``kernels``: for each name in ``kernel_patterns`` (a regex over op
    names) the summed device seconds and call count, averaged per device;
    ``device_ops``: the ``top`` ops by summed time on all devices (per
    device average); ``idle_gaps``: the ``top`` longest gaps on the first
    device, named by the host's overlapping event; ``scopes``:
    ``scope_seconds`` of the names in ``scopes`` (``None`` when no names
    are given or ``xprof`` cannot read the trace); ``idle_by_span``:
    ``idle_by_span`` with the engine's spans. A trace with no device
    operation gives ``devices`` 0 and ``None`` for both, and so does a
    failure of either reading.
    """
    pd = _load(path)
    devices, host_events = [], []
    t_start = t_stop = None
    for plane in pd.planes:
        if _DEVICE_RE.match(plane.name):
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
        return {"devices": 0, "scopes": None, "idle_by_span": None}
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
        "scopes": (None if scopes is None
                   else _reading(scope_seconds, path, scopes, n_dev=n)),
        "idle_by_span": _reading(_idle_by_span, pd),
    }
