"""The harness end to end on the CPU at a tiny size, and its refusals."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run as bench_run
from bench.tiny_cells import REPO, make_root


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
