"""The float32 reference against the program's model, at a tiny size (CPU)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import reference_gaps, reference_logits
from bench.weights import base_key, leaf, leaf_names, make_params
from repro.configs import get_config
from repro.configs.base import LayerSpec
from repro.launch.sharding import make_context
from repro.models.model import Model


def tiny(model: str, dtype="float32"):
    c = get_config(model)
    return dataclasses.replace(
        c, n_layers=2, layers=(LayerSpec(), LayerSpec()), d_model=128,
        n_heads=4, n_kv_heads=2, head_dim=32, d_ff=192, vocab_size=300,
        dtype=dtype)


def sizes_of(c) -> dict:
    return {"hidden_size": c.d_model, "intermediate_size": c.d_ff,
            "num_attention_heads": c.n_heads,
            "num_key_value_heads": c.n_kv_heads, "head_dim": c.head_dim,
            "num_hidden_layers": c.n_layers, "vocab_size": c.vocab_size,
            "rope_theta": c.rope_theta, "rms_norm_eps": 1e-6,
            "qkv_bias": c.qkv_bias, "embed_scale_sqrt_hidden": True,
            "torch_dtype": c.dtype}


def program_logits(cfg, params, tokens, rows):
    """The program's logits at each row, one whole-prompt prefill a row."""
    model, ctx = Model(cfg), make_context(None)
    fn = jax.jit(lambda p, t, i: model.prefill(
        ctx, p, {"tokens": t}, model.init_cache(1, t.shape[1], jnp.float32),
        last_index=i)[0][0])
    return np.stack([np.asarray(fn(params, jnp.asarray(tokens[None]),
                                   jnp.int32(r))) for r in rows])


def test_leaf_by_leaf_equals_one_jitted_call():
    cfg = tiny("qwen2-7b", "bfloat16")
    shapes = jax.eval_shape(Model(cfg).init_params, jax.random.PRNGKey(0))
    params = make_params(shapes, 2**33 + 5)
    key = base_key(2**33 + 5)
    flat = jax.tree_util.tree_leaves(params)
    for name, got in zip(leaf_names(shapes), flat):
        want = jax.jit(lambda k, n=name, s=got.shape: leaf(k, n, s))(key)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32), name)
    assert any(n.endswith("wq/b") for n in leaf_names(shapes))


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "qwen2-7b"])
def test_reference_matches_program_float32(arch):
    cfg = tiny(arch)
    shapes = jax.eval_shape(Model(cfg).init_params, jax.random.PRNGKey(0))
    params = make_params(shapes, 77)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, 40)
    rows = np.array([0, 7, 23, 39])
    with jax.default_matmul_precision("highest"):
        got = program_logits(cfg, params, tokens, rows)
    ref = np.asarray(reference_logits(sizes_of(cfg), 77, tokens[None],
                                      rows[None]))[0]
    rel = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert rel < 1e-4, rel


def test_gaps_zero_for_reference_argmax_and_positive_otherwise():
    cfg = tiny("internlm2-1.8b")
    sz = sizes_of(cfg)
    prompt = np.random.default_rng(1).integers(0, cfg.vocab_size, 12)
    # greedy-decode 6 tokens with the reference itself
    toks = list(prompt)
    for _ in range(6):
        lg = reference_logits(sz, 3, np.array([toks]), np.array([[len(toks) - 1]]))
        toks.append(int(np.argmax(np.asarray(lg)[0, 0])))
    served = np.array(toks[len(prompt):])
    res = reference_gaps(sz, 3, [(prompt, served)], 32, control=True)
    assert np.all(res["gaps"][0] == 0.0)
    assert res["control_gaps"][0].shape == (6,)
    wrong = served.copy()
    wrong[2] = (wrong[2] + 1) % cfg.vocab_size
    res = reference_gaps(sz, 3, [(prompt, wrong)], 32)
    assert res["gaps"][0][2] > 0.0 and res["gaps"][0][0] == 0.0
