"""Plain float32 reference for the dense GQA decoders (internlm2, qwen2).

A configuration file names its reference module (``"reference"``); the
harness asks it for three things: ``program_widths`` (the program-config
attributes the configuration fixes), ``model_flops`` (the model FLOPs a
token costs, for ``step_mfu``) and ``reference_gaps`` (the check that
decides ``correct``). This module is the one for dense GQA decoders.

Follows the published architecture: token embedding, then per layer
RMSNorm -> attention (RoPE, rotate-half form; grouped KV heads; optional
QKV bias) -> residual, RMSNorm -> SwiGLU MLP -> residual; final RMSNorm and
an untied output head. No cache, no batching of requests into one step, no
kernels, no tensor parallelism: every sequence is one causal forward pass
over prompt plus served tokens, in float32 with
``default_matmul_precision("highest")``.

Departures from the published models, each because the system under test
computes it so (the configuration file's ``program_departures``, laid over
its published ``sizes`` before they reach this module):

- the embedding is multiplied by ``sqrt(hidden_size)``
  (``embed_scale_sqrt_hidden``);
- RMSNorm uses ``rms_norm_eps`` 1e-6 (internlm2 publishes 1e-5).

Weights come from ``weights.leaf`` by leaf name, one layer at a time, so
the whole model never sits on the device in float32 and nothing the
program made is read. The configuration's compressed collectives are not
modelled: the reference is the model they approximate.

``control=True`` computes the same forward pass with every matrix product's
inputs rounded to float8 e4m3 (weights per output column, activations per
row, each scaled to the format's range): the nearest precision below the
bfloat16 the configurations state. Attention scores and softmax stay
float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import base_key, leaf, leaf_from_key, leaf_key

__all__ = ["Sizes", "program_widths", "model_flops", "reference_logits",
           "reference_gaps"]

F8_MAX = 448.0


class Sizes:
    """The model sizes of a configuration file (``sizes`` block)."""

    def __init__(self, sizes: dict):
        self.d = int(sizes["hidden_size"])
        self.ff = int(sizes["intermediate_size"])
        self.n_heads = int(sizes["num_attention_heads"])
        self.n_kv = int(sizes["num_key_value_heads"])
        self.hd = int(sizes.get("head_dim") or self.d // self.n_heads)
        self.n_layers = int(sizes["num_hidden_layers"])
        self.vocab = int(sizes["vocab_size"])
        self.theta = float(sizes["rope_theta"])
        self.eps = float(sizes["rms_norm_eps"])
        self.qkv_bias = bool(sizes.get("qkv_bias", False))
        self.embed_scale = (self.d ** 0.5
                            if sizes.get("embed_scale_sqrt_hidden") else 1.0)
        self.dtype = jnp.dtype(sizes.get("torch_dtype", "bfloat16"))

    def layer_shapes(self) -> dict:
        """Leaf shapes of one layer, by name below ``layers/<i>/``."""
        d, ff, q, kv = self.d, self.ff, self.n_heads * self.hd, self.n_kv * self.hd
        s = {"ln1/w": (d,), "core/wq/w": (d, q), "core/wk/w": (d, kv),
             "core/wv/w": (d, kv), "core/wo/w": (q, d), "ln2/w": (d,),
             "mlp/gate/w": (d, ff), "mlp/up/w": (d, ff), "mlp/down/w": (ff, d)}
        if self.qkv_bias:
            s.update({"core/wq/b": (q,), "core/wk/b": (kv,), "core/wv/b": (kv,)})
        return s


def program_widths(sizes: dict) -> dict:
    """The program-config attributes (``repro.configs.ModelConfig``) that
    the configuration's published ``sizes`` fix, with their values."""
    s = sizes
    return {"d_model": s["hidden_size"], "d_ff": s["intermediate_size"],
            "n_heads": s["num_attention_heads"],
            "n_kv_heads": s["num_key_value_heads"], "head_dim": s["head_dim"],
            "n_layers": s["num_hidden_layers"], "vocab_size": s["vocab_size"],
            "rope_theta": s["rope_theta"], "qkv_bias": s["qkv_bias"],
            "tie_embeddings": s["tie_word_embeddings"],
            "dtype": s["torch_dtype"]}


def model_flops(sizes: dict) -> tuple:
    """(FLOPs per real token, FLOPs per emitted token): two a weight of
    every layer's matrices for each token the step processes, two a weight
    of the output head for each token it samples (the step computes logits
    only for sampled rows). Attention's score and value products are left
    out (a few percent at the cells' lengths), so ``step_mfu`` is a lower
    bound."""
    s = sizes
    d, ff = s["hidden_size"], s["intermediate_size"]
    hd = s.get("head_dim") or d // s["num_attention_heads"]
    q, kv = s["num_attention_heads"] * hd, s["num_key_value_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return 2 * per_layer * s["num_hidden_layers"], 2 * s["vocab_size"] * d


def _f8(x, axis):
    """Round to float8 e4m3 with a per-slice scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, control):
    """x (..., in) @ w (in, out) in float32, or from float8 inputs."""
    if control:
        x, w = _f8(x, -1), _f8(w, 0)
    return jnp.einsum("...i,io->...o", x, w)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x (L, H, hd), positions (L,): rotate-half RoPE."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("sz", "control"))
def _layer(x, keys, *, sz: Sizes, control: bool):
    """One decoder layer over a batch of sequences x (B, L, d); ``keys``
    holds each leaf's key, so every layer runs the same program."""
    w = {n: leaf_from_key(keys[n], n, s, sz.dtype).astype(jnp.float32)
         for n, s in sz.layer_shapes().items()}
    L = x.shape[1]
    pos = jnp.arange(L)
    causal = pos[None, :] <= pos[:, None]
    G = sz.n_heads // sz.n_kv

    def one(xs):                                        # (L, d)
        h = _norm(xs, w["ln1/w"], sz.eps)
        q, k, v = (_mm(h, w[f"core/{n}/w"], control) for n in ("wq", "wk", "wv"))
        if sz.qkv_bias:
            q, k, v = q + w["core/wq/b"], k + w["core/wk/b"], v + w["core/wv/b"]
        q = _rope(q.reshape(L, sz.n_heads, sz.hd), pos, sz.theta)
        k = _rope(k.reshape(L, sz.n_kv, sz.hd), pos, sz.theta)
        v = v.reshape(L, sz.n_kv, sz.hd)
        k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * sz.hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v).reshape(L, -1)
        xs = xs + _mm(o, w["core/wo/w"], control)
        h = _norm(xs, w["ln2/w"], sz.eps)
        g = _mm(h, w["mlp/gate/w"], control)
        u = _mm(h, w["mlp/up/w"], control)
        return xs + _mm(jax.nn.silu(g) * u, w["mlp/down/w"], control)

    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("sz",))
def _embed(tokens, key, *, sz: Sizes):
    table = leaf(key, "embed/w", (sz.vocab, sz.d), sz.dtype).astype(jnp.float32)
    return table[tokens] * sz.embed_scale


@functools.partial(jax.jit, static_argnames=("sz", "control"))
def _head(x, rows, key, *, sz: Sizes, control: bool):
    """Logits (B, R, V) at the given rows of each sequence."""
    g = leaf(key, "final_norm/w", (sz.d,), sz.dtype).astype(jnp.float32)
    head = leaf(key, "lm_head/w", (sz.vocab, sz.d), sz.dtype).astype(jnp.float32)
    h = _norm(jnp.take_along_axis(x, rows[..., None], axis=1), g, sz.eps)
    return _mm(h, head.T, control)


def _forward(tokens, rows, key, sz: Sizes, control: bool):
    x = _embed(tokens, key, sz=sz)
    if control:
        x = _f8(x, -1)
    for i in range(sz.n_layers):
        keys = {n: leaf_key(key, f"layers/{i}/{n}") for n in sz.layer_shapes()}
        x = _layer(x, keys, sz=sz, control=control)
    return _head(x, rows, key, sz=sz, control=control)


def reference_logits(sizes: dict, seed: int, tokens, rows, *,
                     control: bool = False):
    """Float32 logits (B, R, V) of sequences ``tokens`` (B, L) at ``rows``
    (B, R) of each (float8 matrix products with ``control``)."""
    sz = Sizes(sizes)
    with jax.default_matmul_precision("highest"):
        return _forward(jnp.asarray(tokens, jnp.int32),
                        jnp.asarray(rows, jnp.int32), base_key(seed), sz,
                        control)


@jax.jit
def _gaps(ref, served):
    """How far each served token's reference logit lies below the best."""
    best = jnp.max(ref, axis=-1)
    return best - jnp.take_along_axis(ref, served[..., None], -1)[..., 0]


def reference_gaps(sizes: dict, seed: int, seqs, length: int, *,
                   control: bool = False) -> dict:
    """Teacher-forced check of served tokens.

    ``seqs`` is a list of (prompt, served) int arrays. Each sequence runs
    once through the reference over ``prompt + served[:-1]``, right-padded
    to ``length``; at every served position the gap is the reference's best
    logit minus the served token's. Returns ``{"gaps": [array per seq]}``
    and, with ``control``, ``"control_gaps"``: the reference's gap of the
    token the float8 forward pass ranks first at the same positions.
    """
    sz = Sizes(sizes)
    key = base_key(seed)
    B = len(seqs)
    R = max(len(s) for _, s in seqs)
    tokens = np.zeros((B, length), np.int32)
    rows = np.zeros((B, R), np.int32)
    served = np.zeros((B, R), np.int32)
    for b, (p, s) in enumerate(seqs):
        full = np.concatenate([p, s[:-1]]).astype(np.int32)
        if len(full) > length:
            raise ValueError(f"sequence of {len(full)} tokens exceeds the "
                             f"reference length {length}")
        tokens[b, :len(full)] = full
        r = len(p) - 1 + np.arange(len(s))
        rows[b, :len(s)] = r
        rows[b, len(s):] = r[-1]
        served[b, :len(s)] = s
        served[b, len(s):] = s[-1]
    with jax.default_matmul_precision("highest"):
        ref = _forward(jnp.asarray(tokens), jnp.asarray(rows), key, sz, False)
        out = {"gaps": np.asarray(_gaps(ref, jnp.asarray(served)))}
        if control:
            ctl = _forward(jnp.asarray(tokens), jnp.asarray(rows), key, sz,
                           True)
            top = jnp.argmax(ctl, axis=-1).astype(jnp.int32)
            out["control_gaps"] = np.asarray(_gaps(ref, top))
            del ctl
        del ref
    n = [len(s) for _, s in seqs]
    return {k: [v[b, :n[b]] for b in range(B)] for k, v in out.items()}
