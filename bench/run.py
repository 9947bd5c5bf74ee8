"""Benchmark one cell of BENCHMARK.json on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<file>``) and a traffic mix
(``bench/traffic/<mix>.json``); its correctness check and limit, the named
scopes its trace reads and any override of the configuration's
compression ``policy`` are in ``bench/cells/<cell>.json``. The
configuration names its architecture's reference module (``"reference"``,
a file under the checkout root), which gives the widths the program must
have, the model FLOPs a token costs and the reference check. The run:

1. turns on JAX's persistent compile cache inside the checkout;
2. makes the weights on the device from ``--seed`` (``bench/weights.py``);
3. builds one ``Engine`` from the configuration and warms up its shapes
   (every gate variant of the mixed step) — all of that is ``setup_s``;
4. drives ``Engine.run`` with the mix's seeded schedule for ``--seconds``,
   then drains (open loop) or stops (backlog), cutting what is unfinished;
5. frees the program's state and checks a sample of the served tokens
   against the configuration's float32 reference.

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (a profiler trace of a few seconds in mid-window) and a
breakdown. A metric reader ``bench/metrics/<name>.py`` gets the run's
record: ``run["stats"]`` holds every counter of the engine's ``ServeStats``
at the window's close, ``run["stats_before_trace"]`` the same from the window's
start to the profiler's start (the close's snapshot waits for the profiler
to stop, so in a traced run it comes late), ``run["traced"]["stats"]``
their change over the traced seconds, ``run["trace"]`` the trace's reduction
(``bench/trace_reduce.py``) with the cell's named scopes. The last stdout
line is the result JSON; the numbers compared for ``correct`` are the last
lines on stderr and the ``check`` key.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

from bench import traffic as traffic_mod  # noqa: E402
from bench.window import Record, window_metrics  # noqa: E402

BENCH = ROOT / "bench"


class NoChip(SystemExit):
    pass


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entry in BENCHMARK.json with its configuration, mix and
    check files loaded."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{[w['name'] for w in spec['workloads']]}")
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    conf = json.loads((root / entry["file"]).read_text())
    if not conf.get("reference"):
        raise SystemExit(f"bench: configuration {entry['file']} names no "
                         f"reference module (key \"reference\")")
    ref_path = root / conf["reference"]
    check = json.loads((root / "bench" / "cells" / f"{name}.json").read_text())
    return {
        "cell": cell,
        "spec": spec,
        "config": conf,
        "reference": load_file(ref_path, f"bench_reference_{ref_path.stem}"),
        # a cell may serve its configuration under another policy (null:
        # dense collectives)
        "policy": check["policy"] if "policy" in check else conf.get("policy"),
        "mix": traffic_mod.load_mix(
            root / "bench" / "traffic" / f"{cell['traffic']}.json"),
        "check": check,
    }


def as_run(conf: dict) -> dict:
    """The model as the program runs it: the published ``sizes`` with the
    program's departures from them (``program_departures``) laid over."""
    dep = {k: v for k, v in (conf.get("program_departures") or {}).items()
           if not k.startswith("_")}
    return {**conf["sizes"], **dep}


def metrics_for(spec: dict, cell: str, kind: str) -> list:
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_file(path: Path, name: str):
    """The Python file at ``path``, loaded as module ``name``."""
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod
    mod_spec.loader.exec_module(mod)
    return mod


def read_metric(name: str, run: dict):
    mod = load_file(BENCH / "metrics" / f"{name}.py",
                    f"bench_metric_{name.replace('.', '_')}")
    value = mod.read(run)
    return None if value is None else float(value)


def device_peak(kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; a kind not in it is an error."""
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if kind not in peaks or kind.startswith("_"):
        raise KeyError(f"bench/peaks.json has no peaks for device kind "
                       f"{kind!r}")
    return peaks[kind]


def require_chips(n: int, allow_cpu: bool = False):
    import jax
    devs = jax.devices()
    if allow_cpu:
        return devs
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: no TPU (JAX found {devs[0].platform!r}); the "
                     f"benchmark runs only on the chip")
    if len(devs) < n:
        raise NoChip(f"bench: the cell needs {n} chips, JAX found "
                     f"{len(devs)}")
    return devs


def build(loaded: dict, seed: int, *, policy_elem=None, model_cfg=None):
    """The configuration's engine, over weights made from the seed."""
    import jax

    from bench.weights import make_params
    from repro.configs import get_config
    from repro.core.formats import MXSpec
    from repro.core.policy import NO_COMPRESSION, CompressionPolicy
    from repro.launch.mesh import make_host_mesh
    from repro.launch.sharding import make_context, param_shardings
    from repro.models.model import Model
    from repro.serving import Engine

    conf = loaded["config"]
    cfg = model_cfg or get_config(conf["model"])
    want = loaded["reference"].program_widths(conf["sizes"])
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise SystemExit(f"bench: the program's {conf['model']} is not the "
                         f"configuration file's: {got} != {want}")
    model = Model(cfg)
    pol = NO_COMPRESSION
    if loaded["policy"]:
        p = loaded["policy"]
        pol = CompressionPolicy(
            spec=MXSpec.make(policy_elem or p["elem"], p["block_size"],
                             p["scale"]),
            variant=p["variant"], use_pallas=p["use_pallas"])
    mesh = make_host_mesh(**conf["mesh"]) if conf.get("mesh") else None
    ctx = make_context(mesh, None, policy=pol)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = make_params(shapes, seed, param_shardings(model, ctx))
    jax.block_until_ready(params)
    e = conf["engine"]
    engine = Engine(model, params, ctx, max_slots=e["max_slots"],
                    max_len=e["max_len"], block_size=e["block_size"],
                    prefill_chunk=e["prefill_chunk"],
                    token_budget=e["token_budget"],
                    cache_spec=e["cache_spec"])
    return engine


def warm_up(engine, vocab: int):
    """Run the cell's shapes once: a prompt one chunk long and a little more
    (a prefill-only step, which takes the compressed variant where the
    policy gates one, then a short step), then decode-only steps (the dense
    variant)."""
    from repro.serving import Request

    rng = np.random.default_rng(0)
    n = engine.prefill_chunk + 3
    engine._state = None       # run() allocates fresh pools: free the old
    engine.run([Request(prompt=rng.integers(0, vocab, n).astype(np.int32),
                        max_new_tokens=4)])
    engine._state = None


def counters_of(stats) -> dict:
    """Every number and every dict of numbers among the attributes of the
    engine's ``ServeStats``, copied; its per-step and per-request lists are
    left out."""
    out = {}
    for k, v in list(vars(stats).items()):
        if isinstance(v, dict):
            v = dict(v)
            if all(isinstance(x, (int, float)) for x in v.values()):
                out[k] = v
        elif isinstance(v, (int, float)):
            out[k] = v
    return out


def counters_change(a: dict, b: dict) -> dict:
    """``counters_of`` snapshot ``b`` less the earlier snapshot ``a``."""
    return {k: ({n: x - a.get(k, {}).get(n, 0) for n, x in v.items()}
                if isinstance(v, dict) else v - a.get(k, 0))
            for k, v in b.items()}


class Monitor(threading.Thread):
    """Side thread on the engine's clock: snapshots the engine's counters
    at the window's close, cuts what is unfinished (at the close for a
    backlog, at the end of the drain for an open loop), and traces a few
    seconds of mid-window when asked, snapshotting the counters just before
    the profiler starts. The close's snapshot waits for ``stop_trace``, so
    in a traced run it can come late."""

    def __init__(self, engine, requests, seconds, drain, open_loop,
                 trace_dir=None, trace_at=None, trace_len=0.0):
        super().__init__(daemon=True)
        self.engine, self.requests = engine, requests
        self.seconds, self.drain, self.open_loop = seconds, drain, open_loop
        self.trace_dir, self.trace_at, self.trace_len = (trace_dir, trace_at,
                                                         trace_len)
        self.prev_t0 = getattr(engine, "_t0", None)
        self.done = threading.Event()
        self.snap_close = self.snap_trace = self.snap_untraced = None
        self.stop_s = None
        self.t0 = None
        self.error = None

    def _snap(self):
        st = self.engine.stats
        return counters_of(st), list(st.step_tokens)

    def _wait_until(self, t):
        while not self.done.is_set():
            left = self.t0 + t - time.perf_counter()
            if left <= 0:
                return True
            self.done.wait(min(left, 0.05))
        return False

    def run(self):
        try:
            while getattr(self.engine, "_t0", None) is self.prev_t0:
                if self.done.wait(0.0005):
                    return
            self.t0 = self.engine._t0
            if self.trace_dir is not None and self._wait_until(self.trace_at):
                import jax

                from bench.trace_reduce import MONITOR_SPAN
                self.snap_untraced = counters_of(self.engine.stats)
                jax.profiler.start_trace(str(self.trace_dir))
                with jax.profiler.TraceAnnotation(MONITOR_SPAN):
                    a = (self._snap(), time.perf_counter() - self.t0)
                    self._wait_until(self.trace_at + self.trace_len)
                    b = (self._snap(), time.perf_counter() - self.t0)
                t_stop = time.perf_counter()
                jax.profiler.stop_trace()
                self.stop_s = time.perf_counter() - t_stop
                self.snap_trace = (a, b)
            if self._wait_until(self.seconds) or self.done.is_set():
                self.snap_close = self._snap()
            if self.open_loop:
                self._wait_until(self.seconds + self.drain)
            for r in self.requests:
                r.cancel()
        except Exception as exc:  # surfaced by the main thread
            self.error = exc
            for r in self.requests:
                r.cancel()


def records_of(requests) -> list:
    out = []
    for r in requests:
        t = r.timing
        if t is None:
            out.append(Record(r.arrival_s, None, [], r.max_new_tokens,
                              "error"))
            continue
        times = []
        if t.first_token_s is not None:
            times = list(t.first_token_s + np.cumsum([0.0] + list(
                t.inter_token_s or [])))
        outcome = {"ok": "ok", "cancelled": "cut"}.get(t.outcome, t.outcome)
        out.append(Record(t.arrival_s, t.admitted_s, times,
                          r.max_new_tokens - t.n_generated, outcome))
    return out


def check_sample(requests, n_req: int, seed: int) -> list:
    """Indices of the requests whose served tokens are checked: the one
    with the most served tokens, then others drawn from the seed."""
    served = [i for i, r in enumerate(requests)
              if r.output is not None and len(r.output) > 0]
    if not served:
        return []
    longest = max(served, key=lambda i: (len(requests[i].prompt)
                                         + len(requests[i].output)))
    rest = [i for i in served if i != longest]
    rng = np.random.default_rng(traffic_mod.seed_words(seed) + [7])
    pick = list(rng.permutation(rest)[:max(0, n_req - 1)])
    return [longest] + [int(i) for i in pick]


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks)) if peaks else 0


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, control: bool = False, trace_dir=None,
             allow_cpu: bool = False, model_cfg=None, policy_elem=None,
             rate=None, check: bool = True, log=print) -> dict:
    """One run of a cell; returns the result object (the last stdout line).
    ``model_cfg`` and ``allow_cpu`` serve the tests (a tiny model on the
    CPU); ``control`` and ``policy_elem`` serve the measurement of the
    correctness limit, ``rate`` (a Poisson rate in place of the mix's) and
    ``check=False`` the knee sweep; the benchmark's own runs use none."""
    import jax

    from repro.launch.cache import enable_compile_cache
    from repro.serving import Request

    loaded = load_cell(name, root)
    cell, mix, conf, chk = (loaded["cell"], loaded["mix"], loaded["config"],
                            loaded["check"])
    devs = require_chips(cell["chips"], allow_cpu)[:cell["chips"]]
    if rate is not None:
        mix = traffic_mod.Mix(**{**mix.__dict__, "rate_per_s": float(rate)})
    enable_compile_cache(root)
    # key compiled programs on their op names too: the trace readers find
    # named scopes there, and a program loaded from a tree whose scopes
    # differ would carry that tree's names
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: compiles.append(
            (time.perf_counter(), ev)) if "/jax/core/compile/" in ev else None)

    engine = build(loaded, seed, policy_elem=policy_elem, model_cfg=model_cfg)
    cfg = engine.cfg
    warm_up(engine, cfg.vocab_size)
    planned = traffic_mod.plan(mix, seconds, seed, cfg.vocab_size)
    requests = [Request(prompt=p.prompt, max_new_tokens=p.max_new_tokens,
                        arrival_s=p.arrival_s) for p in planned]
    own_trace_dir = trace and trace_dir is None
    if own_trace_dir:
        trace_dir = root / "bench" / ".trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
    trace_len = min(5.0, seconds / 4)
    mon = Monitor(engine, requests, seconds, mix.drain_s, mix.open_loop,
                  trace_dir=trace_dir if trace else None,
                  trace_at=seconds / 2 - trace_len / 2, trace_len=trace_len)
    setup_s = time.perf_counter() - T_START
    n_compiles_before = len(compiles)
    mon.start()
    try:
        engine.run(requests)
    finally:
        mon.done.set()
        mon.join()
    if mon.error is not None:
        raise mon.error
    t0 = mon.t0
    in_window = [t for t, _ in compiles[n_compiles_before:]
                 if t0 is not None and t0 <= t <= t0 + seconds]
    memory_peak = peak_bytes(devs)

    recs = records_of(requests)
    win = window_metrics(recs, seconds, open_loop=mix.open_loop)
    stats_close, steps_close = mon.snap_close or (
        counters_of(engine.stats), list(engine.stats.step_tokens))
    n_close, comp_close = (stats_close["n_steps"],
                           stats_close["n_compressed_steps"])
    ref, sizes = loaded["reference"], as_run(conf)
    run = {
        "window": win, "seconds": seconds, "setup_s": setup_s,
        "chips": cell["chips"], "sizes": sizes,
        "model_flops": tuple(ref.model_flops(sizes)),
        "policy": loaded["policy"],
        "steps": {"n": n_close, "budget": engine.token_budget,
                  "real_tokens": sum(p + d for p, d in steps_close),
                  "compressed": comp_close},
        "stats": stats_close,
    }
    outcomes = [r.outcome for r in recs]

    def waiting(t):
        """Requests arrived by t still waiting for their first token."""
        return sum(1 for r in recs if r.arrival_s <= t and not (
            r.token_times and r.token_times[0] <= t))
    def served_rate(a, b):
        return sum(1 for r in recs if r.token_times
                   and a < r.token_times[0] <= b) / (b - a)

    failed = sum(o in ("rejected", "timed_out", "error") for o in outcomes)
    log(f"[{name}] seed {seed}: sent {len(recs)}, ok "
        f"{outcomes.count('ok')}, cut {outcomes.count('cut')}, failed "
        f"{failed}; waiting at mid-window {waiting(seconds / 2)}, at close "
        f"{waiting(seconds)}; first tokens per second in the window's second "
        f"half {served_rate(seconds / 2, seconds):.3f}; steps in window "
        f"{n_close} (compressed "
        f"{comp_close}); "
        f"gate counts {engine.gate_counts}; compiles in window "
        f"{len(in_window)}; arrivals are offsets on the engine's clock, so "
        f"a late scheduler shows as TTFT, not as a late generator; peak "
        f"device bytes {memory_peak}; setup {setup_s:.3f} s",
        file=sys.stderr, flush=True)

    trace_red = None
    if trace:
        from bench.trace_reduce import find_trace, reduce_trace
        path = find_trace(trace_dir) if mon.snap_trace else None
        if path:
            t_red = time.perf_counter()
            trace_red = reduce_trace(path, kernel_patterns=chk.get("kernels"),
                                     scopes=chk.get("scopes"))
            t_red = time.perf_counter() - t_red
            (a_snap, a_t), (b_snap, b_t) = mon.snap_trace
            tok = [t for rec in recs for t in rec.token_times
                   if a_t <= t <= b_t]
            traced_stats = counters_change(a_snap[0], b_snap[0])
            run["trace"] = trace_red
            run["traced"] = {
                "real_tokens": sum(p + d for p, d in b_snap[1][len(a_snap[1]):]),
                "emitted": len(tok),
                "compressed_steps": traced_stats["n_compressed_steps"],
                "stats": traced_stats}
            run["stats_before_trace"] = mon.snap_untraced
            log(f"named scopes, device seconds a chip in the traced "
                f"{trace_red.get('window_s')} s: {trace_red.get('scopes')}; "
                f"first device's idle seconds by host span: "
                f"{trace_red.get('idle_by_span')}; profiler stopped in "
                f"{mon.stop_s:.1f} s, trace reduced in {t_red:.1f} s",
                file=sys.stderr, flush=True)
        run["peak"] = (device_peak(devs[0].device_kind) if not allow_cpu
                       else {"bf16_flops_per_s": 1.0})
        if own_trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # free the program before the reference runs on the same chips
    engine._state = None
    engine.params = None
    del engine
    mon.engine = None
    gc.collect()

    chosen = (check_sample(requests, chk["check_requests"], seed) if check
              else [])
    seqs = [(np.asarray(requests[i].prompt), np.asarray(requests[i].output))
            for i in chosen]
    # the number compared: the widest gap of a served token below the
    # reference's best, or (where that does not separate the program from
    # its control, see PERF.md) the mean gap over the served tokens
    compare = chk.get("compare", "widest_gap")
    limit = float(chk["limit"])
    res, readings, n_served = {}, {}, 0
    if seqs:
        t_ref = time.perf_counter()
        res = ref.reference_gaps(sizes, seed, seqs,
                                 conf["engine"]["max_len"], control=control)
        n_served = int(sum(len(g) for g in res["gaps"]))
        for key, gaps in (("", res["gaps"]),
                          ("control_", res.get("control_gaps"))):
            if gaps:
                readings[key + "widest_gap"] = float(max(g.max() for g in gaps))
                readings[key + "mean_gap"] = float(np.mean(np.concatenate(gaps)))
        log(f"gaps over {n_served} served tokens of requests {chosen}: "
            f"{readings}; reference in {time.perf_counter() - t_ref:.1f} s",
            file=sys.stderr, flush=True)
    # with ``control`` the float8 reference stands in the program's place:
    # its gaps are the ones judged, so a sound limit makes the run incorrect
    judged = "control_" + compare if control else compare
    value = readings.get(judged, float("inf"))
    correct = (value <= limit and failed == 0
               and n_served >= chk["check_min_tokens"])
    check = {compare: {"value": value, "limit": limit},
             "served_tokens_checked": {"value": n_served,
                                       "limit": chk["check_min_tokens"]}}
    if control and compare in readings:
        check["program_" + compare] = {"value": readings[compare],
                                       "limit": limit}

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_for(loaded["spec"], name, kind):
        v = read_metric(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(recs), "failed": failed,
              "metrics": metrics, "device": device}
    if trace and trace_red and trace_red.get("devices"):
        device["busy_s"] = trace_red["busy_s"]
        device["window_s"] = trace_red["window_s"]
        result["breakdown"] = {"device_ops": trace_red["device_ops"][:10],
                               "idle_gaps": trace_red["idle_gaps"][:10]}
    result["check"] = check
    log(f"run ended {time.perf_counter() - T_START:.1f} s after the process "
        f"started", file=sys.stderr, flush=True)
    for k, v in check.items():
        log(f"check {k}: {v['value']} limit {v['limit']}", file=sys.stderr,
            flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the float8 control in the program's place "
                         "(limit measurement only; it should read incorrect)")
    ap.add_argument("--policy-elem", default=None,
                    help="serve with this MX element format in place of the "
                         "configuration's (limit measurement only)")
    ap.add_argument("--trace-dir", default=None)
    ap.add_argument("--rate", type=float, default=None,
                    help="Poisson rate in place of the mix's (knee sweep)")
    ap.add_argument("--no-check", action="store_true",
                    help="skip the reference check (knee sweep)")
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), control=bool(args.control),
                          policy_elem=args.policy_elem, rate=args.rate,
                          check=not args.no_check,
                          trace_dir=(Path(args.trace_dir) if args.trace_dir
                                     else None))
    except NoChip as e:
        print(e, file=sys.stderr, flush=True)
        sys.exit(2)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
