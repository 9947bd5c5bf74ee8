"""Tiny cells for the benchmark's CPU tests: a checkout root holding one
cell of a 2-layer model, with the real harness and metric readers."""
import dataclasses
import json
import shutil
from pathlib import Path

from repro.configs import get_config
from repro.configs.base import LayerSpec

REPO = Path(__file__).resolve().parent.parent


def tiny_cfg(model: str = "internlm2-1.8b"):
    c = get_config(model)
    return dataclasses.replace(
        c, n_layers=2, layers=(LayerSpec(), LayerSpec()), d_model=128,
        n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256, vocab_size=512)


def make_root(root, *, model="internlm2-1.8b", arrival="poisson",
              mesh=None, policy=None, limit=0.1, compare="widest_gap",
              rate=4.0, drain=20.0, reference="bench/reference.py",
              sizes=None, cell=None):
    """Write BENCHMARK.json and the cell's files under ``root``; returns the
    model configuration the program must be built with. ``reference`` is
    the configuration's reference module, relative to ``root`` (copied from
    the repository where ``root`` lacks it; ``None`` leaves the key out);
    ``sizes`` are laid over the configuration's sizes and ``cell`` over its
    cell file."""
    root = Path(root)
    for sub in ("configs", "traffic", "cells"):
        (root / "bench" / sub).mkdir(parents=True, exist_ok=True)
    c = tiny_cfg(model)
    sizes = {"hidden_size": c.d_model, "intermediate_size": c.d_ff,
             "num_attention_heads": c.n_heads,
             "num_key_value_heads": c.n_kv_heads, "head_dim": c.head_dim,
             "num_hidden_layers": c.n_layers, "vocab_size": c.vocab_size,
             "rope_theta": c.rope_theta, "rms_norm_eps": 1e-6,
             "tie_word_embeddings": False, "qkv_bias": c.qkv_bias,
             "embed_scale_sqrt_hidden": True, "torch_dtype": "bfloat16",
             **(sizes or {})}
    chips = 4 if mesh else 1
    conf = {"model": model, "chips": chips, "mesh": mesh, "sizes": sizes,
            "engine": {"max_slots": 4, "block_size": 16, "max_len": 128,
                       "prefill_chunk": 32, "token_budget": 36,
                       "cache_spec": "bf16"},
            "policy": policy}
    if reference is not None:
        conf["reference"] = reference
        if not (root / reference).exists():
            shutil.copy(REPO / reference, root / reference)
    (root / "bench/configs/tiny.json").write_text(json.dumps(conf))
    mix = {"arrival": arrival, "rate_per_s": rate, "count": 6,
           "drain_s": drain,
           "prompt_len": {"dist": "lognormal", "median": 30, "sigma": 0.5,
                          "min": 8, "max": 60},
           "output_len": {"dist": "uniform", "min": 6, "max": 12}}
    (root / "bench/traffic/tiny.json").write_text(json.dumps(mix))
    (root / "bench/cells/tiny-cell.json").write_text(json.dumps(
        {"check_requests": 3, "check_min_tokens": 12, "compare": compare,
         "limit": limit, **(cell or {})}))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "tiny",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "tests"}]
    spec["workloads"] = [{"name": "tiny-cell", "config": "tiny",
                          "traffic": "tiny", "chips": chips, "why": "tests"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return c


def capture_runs(monkeypatch) -> list:
    """The ``run`` records that ``run_cell`` hands its metric readers, one
    a run, in order."""
    from bench import run as bench_run
    runs = []
    orig = bench_run.read_metric

    def read(name, run):
        if not runs or runs[-1] is not run:
            runs.append(run)
        return orig(name, run)

    monkeypatch.setattr(bench_run, "read_metric", read)
    return runs
