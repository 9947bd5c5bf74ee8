"""A configuration names its architecture's reference module: the widths
the program must have, the model FLOPs a token costs and the check that
decides ``correct`` all come from it (tiny cells, CPU).

The dense module must read exactly as the harness's fixed dense code did
before, for both configurations; a second architecture (a stub module
written under ``tmp_path``) must run through ``run_cell`` with no edit of
the harness."""
import dataclasses
import json
import textwrap

import numpy as np
import pytest

from bench import reference as dense_ref
from bench import run as bench_run
from bench.metrics.step_mfu_common import step_mfu
from bench.test_bench_reference import sizes_of, tiny
from bench.tiny_cells import REPO, capture_runs, make_root
from repro.configs.base import LayerSpec

CONFIGS = {"internlm2-1.8b-1chip": "internlm2-chat",
           "qwen2-7b-tp4-mxfp4": "qwen2-tp4-rag"}


def _quiet(*a, **k):
    pass


def _conf(config):
    return json.loads((REPO / "bench" / "configs" / f"{config}.json")
                      .read_text())


def old_want(s):
    """The width check ``bench/run.py:build`` made before the reference
    module gave it."""
    return {"d_model": s["hidden_size"], "d_ff": s["intermediate_size"],
            "n_heads": s["num_attention_heads"],
            "n_kv_heads": s["num_key_value_heads"], "head_dim": s["head_dim"],
            "n_layers": s["num_hidden_layers"], "vocab_size": s["vocab_size"],
            "rope_theta": s["rope_theta"], "qkv_bias": s["qkv_bias"],
            "tie_embeddings": s["tie_word_embeddings"],
            "dtype": s["torch_dtype"]}


def old_step_mfu(run):
    """``step_mfu`` as it counted FLOPs before the reference module did."""
    tr, tok = run.get("trace"), run.get("traced")
    s = run["sizes"]
    d, ff = s["hidden_size"], s["intermediate_size"]
    hd = s.get("head_dim") or d // s["num_attention_heads"]
    q, kv = s["num_attention_heads"] * hd, s["num_key_value_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    flops = (2 * per_layer * s["num_hidden_layers"] * tok["real_tokens"]
             + 2 * s["vocab_size"] * d * tok["emitted"])
    peak = run["peak"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * flops / (tr["window_s"] * peak)


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_program_widths_are_the_old_width_check(config):
    conf = _conf(config)
    loaded = bench_run.load_cell(CONFIGS[config])
    assert conf["reference"] == "bench/reference.py"
    assert loaded["reference"].program_widths(conf["sizes"]) == old_want(
        conf["sizes"])


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_step_mfu_is_the_old_formula(config):
    conf = _conf(config)
    loaded = bench_run.load_cell(CONFIGS[config])
    sizes = bench_run.as_run(conf)
    run = {"sizes": sizes, "chips": conf["chips"],
           "model_flops": loaded["reference"].model_flops(sizes),
           "trace": {"window_s": 5.0173}, "peak": {"bf16_flops_per_s": 197e12},
           "traced": {"real_tokens": 61_234, "emitted": 1_877}}
    assert step_mfu(run) == old_step_mfu(run)


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_loaded_reference_gaps_are_the_dense_module_s(config):
    # the module the configuration names, loaded from its file, gives the
    # same gaps as ``bench.reference`` at a tiny size of the same family
    mod = bench_run.load_cell(CONFIGS[config])["reference"]
    sz = sizes_of(tiny(_conf(config)["model"]))
    rng = np.random.default_rng(5)
    seqs = [(rng.integers(0, 300, n), rng.integers(0, 300, 4))
            for n in (9, 17)]
    got = mod.reference_gaps(sz, 2**33 + 1, seqs, 32)
    want = dense_ref.reference_gaps(sz, 2**33 + 1, seqs, 32)
    for g, w in zip(got["gaps"], want["gaps"]):
        np.testing.assert_array_equal(g, w)


def test_configuration_without_reference_is_refused_before_weights(
        tmp_path, monkeypatch):
    import bench.weights

    def no_weights(*a, **k):
        raise AssertionError("weights made for a configuration with no "
                             "reference module")

    monkeypatch.setattr(bench.weights, "make_params", no_weights)
    cfg = make_root(tmp_path, reference=None)
    with pytest.raises(SystemExit, match="names no reference module"):
        bench_run.run_cell("tiny-cell", 3, 1.0, False, root=tmp_path,
                           allow_cpu=True, model_cfg=cfg, log=_quiet)


# A second architecture, as a later configuration would bring it: queries
# and keys RMS-normed per head (two more leaves a layer), a sliding window
# on the layers ``window_layers`` lists, a width check on ``qk_norm`` and a
# FLOP count with the windowed attention products.
STUB = textwrap.dedent('''
    """Float32 reference for a GQA decoder with qk-norm and sliding-window
    layers (tests of the harness)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import reference as dense
    from bench.weights import base_key, leaf_from_key, leaf_key


    class Sizes(dense.Sizes):
        def __init__(self, sizes):
            super().__init__(sizes)
            self.window = int(sizes["sliding_window"])
            self.window_layers = tuple(sizes["window_layers"])

        def layer_shapes(self):
            return {**super().layer_shapes(), "core/q_norm/w": (self.hd,),
                    "core/k_norm/w": (self.hd,)}


    def program_widths(sizes):
        return {**dense.program_widths(sizes), "qk_norm": True}


    def model_flops(sizes):
        per_real, per_emitted = dense.model_flops(sizes)
        attn = (4 * sizes["num_attention_heads"] * sizes["head_dim"]
                * sizes["sliding_window"] * sizes["num_hidden_layers"])
        return per_real + attn, per_emitted


    @functools.partial(jax.jit, static_argnames=("sz", "window"))
    def _layer(x, keys, *, sz, window):
        w = {n: leaf_from_key(keys[n], n, s, sz.dtype).astype(jnp.float32)
             for n, s in sz.layer_shapes().items()}
        L = x.shape[1]
        pos = jnp.arange(L)
        mask = ((pos[None, :] <= pos[:, None])
                & (pos[None, :] > pos[:, None] - window))
        G = sz.n_heads // sz.n_kv

        def one(xs):
            h = dense._norm(xs, w["ln1/w"], sz.eps)
            q, k, v = (h @ w[f"core/{n}/w"] for n in ("wq", "wk", "wv"))
            q = dense._norm(q.reshape(L, sz.n_heads, sz.hd),
                            w["core/q_norm/w"], sz.eps)
            k = dense._norm(k.reshape(L, sz.n_kv, sz.hd),
                            w["core/k_norm/w"], sz.eps)
            q, k = dense._rope(q, pos, sz.theta), dense._rope(k, pos, sz.theta)
            k = jnp.repeat(k, G, axis=1)
            v = jnp.repeat(v.reshape(L, sz.n_kv, sz.hd), G, axis=1)
            s = jnp.einsum("qhd,khd->hqk", q, k) * sz.hd ** -0.5
            p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
            o = jnp.einsum("hqk,khd->qhd", p, v).reshape(L, -1)
            xs = xs + o @ w["core/wo/w"]
            h = dense._norm(xs, w["ln2/w"], sz.eps)
            g = jax.nn.silu(h @ w["mlp/gate/w"]) * (h @ w["mlp/up/w"])
            return xs + g @ w["mlp/down/w"]

        return jax.lax.map(one, x)


    def reference_gaps(sizes, seed, seqs, length, *, control=False):
        sz, key = Sizes(sizes), base_key(seed)
        R = max(len(s) for _, s in seqs)
        tokens = np.zeros((len(seqs), length), np.int32)
        rows = np.zeros((len(seqs), R), np.int32)
        served = np.zeros((len(seqs), R), np.int32)
        for b, (p, s) in enumerate(seqs):
            full = np.concatenate([p, s[:-1]])
            tokens[b, :len(full)] = full
            rows[b] = len(p) - 1 + np.minimum(np.arange(R), len(s) - 1)
            served[b] = s[np.minimum(np.arange(R), len(s) - 1)]
        with jax.default_matmul_precision("highest"):
            x = dense._embed(jnp.asarray(tokens), key, sz=sz)
            for i in range(sz.n_layers):
                keys = {n: leaf_key(key, f"layers/{i}/{n}")
                        for n in sz.layer_shapes()}
                x = _layer(x, keys, sz=sz, window=(
                    sz.window if i in sz.window_layers else length))
            ref = dense._head(x, jnp.asarray(rows), key, sz=sz,
                              control=False)
            gaps = np.asarray(dense._gaps(ref, jnp.asarray(served)))
        return {"gaps": [gaps[b, :len(s)] for b, (_, s) in enumerate(seqs)]}
''')

WINDOW = 8


def _stub_root(root, reference):
    (root / "bench").mkdir(parents=True, exist_ok=True)
    (root / "bench" / "reference_qknorm_window.py").write_text(STUB)
    cfg = make_root(root, reference=reference,
                    sizes={"sliding_window": WINDOW, "window_layers": [0]})
    return dataclasses.replace(cfg, qk_norm=True, layers=(
        LayerSpec(window=WINDOW), LayerSpec()))


def test_second_architecture_runs_through_its_own_files(tmp_path,
                                                        monkeypatch):
    runs = capture_runs(monkeypatch)
    stub = "bench/reference_qknorm_window.py"
    cfg = _stub_root(tmp_path / "stub", stub)
    res = bench_run.run_cell("tiny-cell", 2**31 + 5, 3.0, False,
                             root=tmp_path / "stub", allow_cpu=True,
                             model_cfg=cfg, log=_quiet)
    # prompts of 8-60 tokens against a window of 8: the stub's windowed
    # reference is the one that agrees with the program
    assert res["correct"] is True, res["check"]
    assert res["check"]["served_tokens_checked"]["value"] >= 12
    gap = res["check"]["widest_gap"]["value"]
    assert gap <= 0.1
    # its FLOP count reaches step_mfu
    run = runs[-1]
    mod = bench_run.load_cell("tiny-cell", tmp_path / "stub")["reference"]
    assert run["model_flops"] == mod.model_flops(run["sizes"])
    assert run["model_flops"] != dense_ref.model_flops(run["sizes"])
    traced = {**run, "trace": {"window_s": 2.0},
              "traced": {"real_tokens": 1000, "emitted": 10},
              "peak": {"bf16_flops_per_s": 1e12}}
    per_real, per_emitted = mod.model_flops(run["sizes"])
    assert step_mfu(traced) == 100.0 * (per_real * 1000 + per_emitted * 10) / (
        2.0 * 1e12)

    # the same program judged by the dense reference reads far off: the
    # configuration's module, not a fixed one, decides ``correct``
    cfg = _stub_root(tmp_path / "dense", "bench/reference.py")
    res = bench_run.run_cell("tiny-cell", 2**31 + 5, 3.0, False,
                             root=tmp_path / "dense", allow_cpu=True,
                             model_cfg=cfg, log=_quiet)
    assert res["correct"] is False
    assert res["check"]["widest_gap"]["value"] > 3 * max(gap, 0.1)


def test_second_architecture_s_width_check_holds_its_extra_key(tmp_path):
    cfg = _stub_root(tmp_path, "bench/reference_qknorm_window.py")
    with pytest.raises(SystemExit, match="qk_norm"):
        bench_run.run_cell("tiny-cell", 3, 1.0, False, root=tmp_path,
                           allow_cpu=True,
                           model_cfg=dataclasses.replace(cfg, qk_norm=False),
                           log=_quiet)
