"""``correct`` comes out false when the timed path is broken, and the
controls read above the limit that sound runs read below (tiny cells, CPU).

Each fault is planted in the program underneath an otherwise whole run of
the harness: a step that returns its state unchanged, half of each step's
batch left out, a served token altered where it is sampled, and (on four
host devices) the exchange between chips left out.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import pytest

from bench import run as bench_run
from bench.tiny_cells import REPO, make_root
from repro.models.model import Model
from repro.serving.engine import Engine

LIMIT = 0.1     # tiny bf16 cells read about 0.015; the float8 control ~0.24


def _run(root, cfg, **kw):
    return bench_run.run_cell("tiny-cell", 4242, 3.0, False, root=root,
                              allow_cpu=True, model_cfg=cfg,
                              log=lambda *a, **k: None, **kw)


def test_float8_control_fails_where_the_program_passes(tmp_path):
    cfg = make_root(tmp_path, limit=LIMIT)
    # the control is judged in the program's place and must read incorrect;
    # the program's own reading of the same run stays under the limit
    res = _run(tmp_path, cfg, control=True)
    assert res["correct"] is False
    assert res["check"]["widest_gap"]["value"] > LIMIT
    assert res["check"]["program_widest_gap"]["value"] <= LIMIT


def _state_unchanged(orig):
    def step(self, ctx, params, tokens, state, *a, **k):
        logits, _ = orig(self, ctx, params, tokens, state, *a, **k)
        return logits, state
    return step


def _half_batch(orig):
    def step(self, ctx, params, tokens, state, slot_ids, positions, valid,
             *a, **k):
        keep = jnp.arange(valid.shape[0]) < valid.shape[0] // 2
        return orig(self, ctx, params, tokens, state, slot_ids, positions,
                    valid & keep, *a, **k)
    return step


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_fault_in_timed_path_reads_incorrect(tmp_path, monkeypatch, fault):
    cfg = make_root(tmp_path, limit=LIMIT)
    if fault == "token_altered":
        orig = Engine._sample_impl
        monkeypatch.setattr(Engine, "_sample_impl", staticmethod(
            lambda lg, t, k: (orig(lg, t, k) + 1) % lg.shape[-1]))
    else:
        wrap = _state_unchanged if fault == "state_unchanged" else _half_batch
        monkeypatch.setattr(Model, "mixed_step", wrap(Model.mixed_step))
    res = _run(tmp_path, cfg)
    assert res["correct"] is False
    assert res["check"]["widest_gap"]["value"] > LIMIT


TP_SCRIPT = textwrap.dedent("""
    import json, os, sys
    sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
    from pathlib import Path
    import repro.core.tp as tp
    from bench import run as R
    from bench.tiny_cells import make_root
    pol = {"elem": "fp4_e2m1", "block_size": 32, "scale": "e8m0",
           "variant": "gather", "use_pallas": True}
    out = {}
    for label, elem in [("program", None), ("control", "fp3_e1m1"),
                        ("no_exchange", None)]:
        if label == "no_exchange":
            tp.psum_maybe_compressed = lambda part, *a, **k: part
        root = Path(sys.argv[2]) / label
        cfg = make_root(root, model="qwen2-7b", policy=pol, limit=0.3,
                        mesh={"data": 1, "model": 4}, arrival="backlog")
        res = R.run_cell("tiny-cell", 11, 20.0, False, root=root,
                         allow_cpu=True, model_cfg=cfg, policy_elem=elem,
                         log=lambda *a, **k: None)
        out[label] = [res["correct"], res["check"]["widest_gap"]["value"]]
    print(json.dumps(out))
""")


def test_four_chip_control_and_missing_exchange(tmp_path):
    """fp4 collectives pass and a step whose row-parallel reductions skip
    the exchange between chips reads incorrect. The fp3 control reads
    further from the reference than fp4 on the same seed; at this tiny size
    (2 layers, width 128) the two do not separate by a limit, which the
    full-size cell's mean gap does on the chip (PERF.md). Every request is
    present at the start, so which steps take the compressed variant does
    not hang on how fast the machine runs: with Poisson arrivals a loaded
    machine packed the steps otherwise and fp3 once read under fp4."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", TP_SCRIPT, str(REPO),
                        str(tmp_path)], env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["program"][0] is True, out
    assert out["control"][1] > out["program"][1], out
    assert out["no_exchange"][0] is False, out
