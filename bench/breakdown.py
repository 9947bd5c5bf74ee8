"""Where one cell's time goes, by the program's own spans, counters and
named scopes.

    python3 bench/breakdown.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does (``run.run_cell``;
``--no-check`` skips the reference check, as there), keeping the engine's
phase counters (``ServeStats.phase_s`` / ``phase_n``) at each of the
harness's snapshots and the profiler trace. It prints the run's result,
then one JSON line:

- ``host_ms_per_step``: (``engine.admit`` + ``engine.schedule`` +
  ``engine.dispatch`` + ``engine.emit``) host seconds at the window's close
  over its steps; with
  ``phase_ms_per_step`` for each span, the spans' counts ``phase_n``, and
  the same over the traced seconds (``traced``);
- ``scopes``: device seconds a chip under each named scope
  (``paged_attention``, ``tp_codec``), from the framework op name of each
  operation through ``xprof``'s ``hlo_stats`` (a layout copy the compiler
  made without one goes to the scope around it, read from the HLO modules
  in the trace); ``shares``: each over the traced window;
- ``idle_by_span``: the first device's idle seconds in the traced window,
  split by the innermost ``engine.*`` span the host was in (``none``
  outside all of them).

A program without these spans or scopes (an older tree) reads ``None`` or
nothing for them. The compile cache key includes op metadata here, so the
scopes read are those of this tree's compile.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run as bench_run  # noqa: E402  (puts src/ on the path)
from bench.trace_reduce import _events, _gaps, _load, find_trace  # noqa: E402

SCOPES = ("paged_attention", "tp_codec")
HOST_PHASES = ("engine.admit", "engine.schedule", "engine.dispatch",
               "engine.emit")
PHASES = ("engine.admit", "engine.step", "engine.schedule", "engine.dispatch",
          "engine.wait", "engine.emit", "engine.idle")


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


def inherited_scopes(graph: dict, name: str, scopes) -> frozenset:
    """Scopes of an instruction the compiler made without a framework op
    name (a layout copy): those shared by the nearest named instructions on
    both sides of it (through other unnamed ones), its producers' and its
    users'; a side with none found is left out."""
    users = {}
    for n, (_, operands) in graph.items():
        for o in operands:
            users.setdefault(o, []).append(n)
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


def scope_seconds(path, scopes=SCOPES):
    """Device seconds a chip of the ops whose framework op name has each
    scope as a path component (``jit(f)/paged_attention/dot_general``),
    from ``xprof``'s ``hlo_stats`` self times, an op the compiler made
    without such a name counted where ``inherited_scopes`` puts it; ``None``
    for a trace with no device op. ``all`` is every op's self time, the
    scopes' upper bound; ``unnamed`` is the part with no name of its own
    that no scope took. ``hlo_stats`` writes a cache beside the trace it
    reads, so it reads a copy."""
    from xprof.convert.raw_to_tool_data import xspace_to_tool_data

    n_dev = sum(1 for p in _load(path).planes
                if p.name.startswith("/device:TPU:"))
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
    graphs = None
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
            graph = graphs.get(int(cell["program_id"]), {})
            got = inherited_scopes(graph, str(cell["hlo_op_name"]), scopes)
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
    to stop_trace, as ``trace_reduce``), split by the innermost host span
    whose name starts with ``prefix``; ``none`` is idle outside them all.
    ``None`` for a trace with no device op."""
    pd = _load(path)
    devices, spans = [], []
    lo = hi = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [x for line in plane.lines if line.name == "XLA Ops"
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


def host_ms_per_step(phase_s, n_steps):
    """(admit + schedule + dispatch + emit) host ms a step; ``None``
    without the engine's phase counters or steps."""
    if not phase_s or not n_steps or not any(p in phase_s
                                             for p in HOST_PHASES):
        return None
    return sum(phase_s.get(p, 0.0) for p in HOST_PHASES) / n_steps * 1e3


def phase_ms_per_step(phase_s, n_steps):
    if not phase_s or not n_steps:
        return None
    return {p: phase_s.get(p, 0.0) / n_steps * 1e3 for p in PHASES}


class PhaseMonitor(bench_run.Monitor):
    """The harness's monitor, also copying the engine's phase counters at
    each of its snapshots (trace start, trace stop, the window's close)."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.phases = []

    def _snap(self):
        snap = super()._snap()
        st = self.engine.stats
        self.phases.append((snap[0], dict(getattr(st, "phase_s", {})),
                            dict(getattr(st, "phase_n", {}))))
        return snap


def _reduce(mon: PhaseMonitor, path, dev: dict) -> dict:
    out = {}
    if mon.snap_close is not None and mon.phases:
        n, ph, count = mon.phases[-1]
        out["host_ms_per_step"] = host_ms_per_step(ph, n)
        out["phase_ms_per_step"] = phase_ms_per_step(ph, n)
        out["phase_n"] = count
    if mon.snap_trace is not None and len(mon.phases) >= 2:
        (n_a, ph_a, _), (n_b, ph_b, _) = mon.phases[:2]
        d = {p: ph_b.get(p, 0.0) - ph_a.get(p, 0.0) for p in ph_b}
        out["traced"] = {"steps": n_b - n_a,
                         "host_ms_per_step": host_ms_per_step(d, n_b - n_a),
                         "phase_ms_per_step": phase_ms_per_step(d, n_b - n_a)}
    if path:
        sc = out["scopes"] = scope_seconds(path)
        if sc and dev.get("window_s"):
            out["shares"] = {s: sc[s] / dev["window_s"] for s in SCOPES}
        out["idle_by_span"] = idle_by_span(path)
    out["busy_s"], out["window_s"] = dev.get("busy_s"), dev.get("window_s")
    return out


def breakdown(name: str, seed: int, seconds: float, *, trace_dir=None,
              log=print, **kw) -> tuple:
    """One traced run of cell ``name`` (``kw`` go to ``run.run_cell``);
    returns (the run's result, the breakdown). The trace is kept in
    ``trace_dir`` when one is given."""
    import jax

    key_flag = "jax_compilation_cache_include_metadata_in_key"
    keyed = getattr(jax.config, key_flag)
    made = []
    orig = bench_run.Monitor

    def monitor(*a, **k):
        made.append(PhaseMonitor(*a, **k))
        return made[-1]

    tmp = trace_dir or tempfile.mkdtemp(prefix="breakdown-")
    bench_run.Monitor = monitor
    jax.config.update(key_flag, True)
    try:
        res = bench_run.run_cell(name, seed, seconds, True, trace_dir=tmp,
                                 log=log, **kw)
        return res, _reduce(made[0], find_trace(tmp), res["device"])
    finally:
        bench_run.Monitor = orig
        jax.config.update(key_flag, keyed)
        if not trace_dir:
            shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-dir", default=None, help="keep the trace here")
    ap.add_argument("--no-check", action="store_true",
                    help="skip the reference check")
    args = ap.parse_args(argv)
    try:
        res, out = breakdown(args.workload, args.seed, args.seconds,
                             check=not args.no_check,
                             trace_dir=args.trace_dir)
    except bench_run.NoChip as e:
        print(e, file=sys.stderr, flush=True)
        sys.exit(2)
    print(json.dumps(res), flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
