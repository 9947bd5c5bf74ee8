"""The harness end to end on the CPU at a tiny size, and its refusals."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from bench import run as bench_run
from bench.tiny_cells import REPO, capture_runs, make_root
from repro.serving.ttft import ServeStats


def _run(root, cfg, **kw):
    return bench_run.run_cell("tiny-cell", kw.pop("seed", 2**31 + 99),
                              kw.pop("seconds", 3.0), kw.pop("trace", False),
                              root=root, allow_cpu=True, model_cfg=cfg,
                              log=lambda *a, **k: None, **kw)


def test_open_loop_cell_end_to_end(tmp_path):
    cfg = make_root(tmp_path)
    res = _run(tmp_path, cfg)
    assert res["correct"] is True
    assert res["attempted"] == 12 and res["failed"] == 0
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    assert set(res["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    for name, m in res["metrics"].items():
        assert m["value"] > 0, name
    assert list(res)[-1] == "check"
    assert res["check"]["widest_gap"]["value"] <= 0.1
    assert res["device"]["platform"] == "cpu"


def test_traced_cell_reports_counter_metrics(tmp_path):
    cfg = make_root(tmp_path, arrival="backlog")
    res = _run(tmp_path, cfg, trace=True, trace_dir=tmp_path / "tr")
    assert res["correct"] is True and res["attempted"] == 6
    # the CPU has no device plane, so trace readers find nothing and are
    # left out rather than reported as 0
    assert {"budget_fill", "step_ms", "queue_wait_p50_ms"} <= set(
        res["metrics"])
    assert "device_idle_share" not in res["metrics"]
    assert 0 < res["metrics"]["budget_fill"]["value"] <= 1


def test_cut_requests_are_not_failures(tmp_path):
    cfg = make_root(tmp_path, rate=300.0, drain=0.0)
    res = _run(tmp_path, cfg, seconds=1.0)
    assert res["failed"] == 0 and res["attempted"] == 300
    # far more arrivals than the engine serves in a second: the TTFT tail
    # is a request that never got its first token
    assert res["metrics"]["ttft_p90_ms"]["value"] == float("inf")


def _cli(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu"}, **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "internlm2-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _cli(REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_unknown_device_kind_has_no_peak():
    with pytest.raises(KeyError, match="no peaks"):
        bench_run.device_peak("TPU v99")
    assert bench_run.device_peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12


@pytest.mark.parametrize("config,eps", [("internlm2-1.8b-1chip", 1e-5),
                                        ("qwen2-7b-tp4-mxfp4", 1e-6)])
def test_program_departures_lie_over_the_published_sizes(config, eps):
    conf = json.loads((REPO / "bench" / "configs" / f"{config}.json")
                      .read_text())
    assert conf["sizes"]["rms_norm_eps"] == eps
    assert "embed_scale_sqrt_hidden" not in conf["sizes"]
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    assert next(c for c in spec["configs"]
                if c["name"] == config)["reduced"] == []
    run = bench_run.as_run(conf)
    assert run["rms_norm_eps"] == 1e-6 and run["embed_scale_sqrt_hidden"]
    assert "_why" not in run
    assert {k: run[k] for k in conf["sizes"] if k != "rms_norm_eps"} == {
        k: v for k, v in conf["sizes"].items() if k != "rms_norm_eps"}


def _reader(name):
    return bench_run.load_file(REPO / "bench" / "metrics" / f"{name}.py",
                               f"test_metric_{name}").read


def test_readers_without_their_data_read_none():
    host = _reader("host_ms_per_step")
    tiles = _reader("read_tile_share")
    paged = _reader("paged_attention_share")
    assert host({}) is None
    assert host({"stats_before_trace": {"phase_s": {}, "n_steps": 10}}) is None
    assert host({"stats_before_trace": {"phase_s": {"engine.step": 1.0},
                                        "n_steps": 10}}) is None
    assert host({"stats_before_trace": {"phase_s": {"engine.schedule": 1.0},
                                        "n_steps": 0}}) is None
    stats = {"phase_s": {"engine.admit": 0.1, "engine.schedule": 0.2,
                         "engine.wait": 5.0, "engine.emit": 0.1},
             "n_steps": 100}
    assert host({"stats_before_trace": stats}) == pytest.approx(4)
    # the close's counters, which wait for the profiler's stop, are not read
    assert host({"stats": stats}) is None
    assert tiles({"stats_before_trace": {"n_steps": 3}}) is None
    assert tiles({"stats_before_trace": {"read_tiles": 0,
                                         "read_tiles_held": 0}}) is None
    assert tiles({"stats_before_trace": {"read_tiles": 20,
                                         "read_tiles_held": 80}}) == 0.25
    assert tiles({"stats": {"read_tiles": 20, "read_tiles_held": 80}}) is None
    # no trace, no scopes read, or a scope that ran nothing
    assert paged({}) is None
    assert paged({"trace": {"window_s": 5.0, "scopes": None}}) is None
    assert paged({"trace": {"window_s": 5.0, "scopes": {
        "paged_attention": 0.0}}}) is None
    assert paged({"trace": {"window_s": 5.0, "scopes": {
        "paged_attention": 4.0}}}) == 0.8


def test_traced_tiny_cell_reads_spans_and_counters(tmp_path, monkeypatch):
    # Poisson arrivals: requests land inside the traced mid-window second
    runs = capture_runs(monkeypatch)
    cfg = make_root(tmp_path, cell={"scopes": ["paged_attention"]})
    res = _run(tmp_path, cfg, seed=2**31 + 7, trace=True,
               trace_dir=tmp_path / "tr", check=False)
    assert res["attempted"] == 12 and "step_ms" in res["metrics"]
    run = runs[-1]
    phases = run["stats"]["phase_s"]
    assert all(phases[p] > 0 for p in (
        "engine.admit", "engine.schedule", "engine.dispatch", "engine.emit",
        "engine.wait"))
    assert run["stats"]["phase_n"]["engine.step"] >= run["stats"][
        "phase_n"]["engine.emit"] > 0
    # host time and tiles from the window's start to the profiler's start
    host = res["metrics"]["host_ms_per_step"]["value"]
    before = run["stats_before_trace"]
    assert host == pytest.approx(sum(
        before["phase_s"][p] for p in ("engine.admit", "engine.schedule",
                                       "engine.dispatch", "engine.emit"))
        / before["n_steps"] * 1e3)
    assert res["metrics"]["read_tile_share"]["value"] == (
        before["read_tiles"] / before["read_tiles_held"])
    assert 0 < res["metrics"]["read_tile_share"]["value"] <= 1
    # the traced seconds' change of every counter, after the snapshot
    traced = run["traced"]["stats"]
    assert 0 < traced["n_steps"]
    assert before["n_steps"] + traced["n_steps"] <= run["stats"]["n_steps"]
    assert traced["phase_s"]["engine.step"] > 0
    assert traced["n_compressed_steps"] == run["traced"]["compressed_steps"]
    # the CPU has no device plane: the trace readers find nothing
    assert run["trace"]["scopes"] is None
    assert run["trace"]["idle_by_span"] is None
    assert "paged_attention_share" not in res["metrics"]


def test_engine_counters_reach_readers(tmp_path, monkeypatch):
    # a counter a later program adds to ServeStats reaches the readers in
    # ``run["stats"]`` with no edit of the harness; lists stay out
    init, record = ServeStats.__init__, ServeStats.record_step

    def counted_init(self):
        init(self)
        self.expert_tokens = 0
        self.expert_load = {"e0": 0, "e1": 0.0}

    def counted_step(self, n_prefill, n_decode, *a, **k):
        record(self, n_prefill, n_decode, *a, **k)
        self.expert_tokens += n_prefill + n_decode
        self.expert_load["e1"] += 0.5

    monkeypatch.setattr(ServeStats, "__init__", counted_init)
    monkeypatch.setattr(ServeStats, "record_step", counted_step)
    runs = capture_runs(monkeypatch)
    cfg = make_root(tmp_path)
    _run(tmp_path, cfg, check=False)
    stats = runs[-1]["stats"]
    assert stats["expert_tokens"] == runs[-1]["steps"]["real_tokens"] > 0
    assert stats["expert_load"] == {"e0": 0, "e1": 0.5 * stats["n_steps"]}
    assert {"n_steps", "n_dispatches", "n_compressed_steps", "read_tiles",
            "read_tiles_held", "phase_s", "phase_n"} <= set(stats)
    assert "step_tokens" not in stats and "timings" not in stats


POLICY_SCRIPT = textwrap.dedent("""
    import json, os, sys
    sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
    from pathlib import Path
    from bench import run as R
    from bench.tiny_cells import make_root
    pol = {"elem": "fp4_e2m1", "block_size": 32, "scale": "e8m0",
           "variant": "gather", "use_pallas": True}
    runs, orig = [], R.read_metric
    R.read_metric = lambda n, run: (runs.append(run), orig(n, run))[1]
    out = {}
    for label, cell in [("configuration", None), ("dense", {"policy": None})]:
        root = Path(sys.argv[2]) / label
        cfg = make_root(root, model="qwen2-7b", policy=pol, limit=0.3,
                        mesh={"data": 1, "model": 4}, arrival="backlog",
                        cell=cell)
        res = R.run_cell("tiny-cell", 13, 4.0, True, root=root,
                         trace_dir=root / "tr", allow_cpu=True,
                         model_cfg=cfg, check=False,
                         log=lambda *a, **k: None)
        share = res["metrics"].get("compressed_step_share")
        out[label] = {"share": share and share["value"],
                      "compressed": runs[-1]["steps"]["compressed"],
                      "steps": runs[-1]["steps"]["n"],
                      "policy": runs[-1]["policy"]}
    print(json.dumps(out))
""")


def test_cell_policy_null_serves_dense(tmp_path):
    """A cell file's ``"policy": null`` serves its configuration dense: no
    compressed step, no ``compressed_step_share``, ``run["policy"]`` None;
    without the key the configuration's policy compresses prefill steps
    (four host devices, every request present at the start)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", POLICY_SCRIPT, str(REPO),
                        str(tmp_path)], env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    conf, dense = out["configuration"], out["dense"]
    assert conf["policy"]["elem"] == "fp4_e2m1" and conf["compressed"] > 0
    assert conf["share"] == conf["compressed"] / conf["steps"]
    assert dense["policy"] is None and dense["compressed"] == 0
    assert dense["share"] is None and dense["steps"] > 0
