"""Random weights from the seed, made by the benchmark and not the program.

Every leaf is a function of (seed, leaf name, shape) alone, so the program's
copy (all leaves in one jitted call, straight into their shardings, in the
type they are served in) and the reference's copy (one layer at a time)
hold the same bits. Leaf names follow the program's parameter tree
(``layers/3/core/wq/w``); the reference addresses leaves by the same names.

Scales: matrices ``N(0, 1/fan_in)`` (embedding and head tables by their
width), norm gains ``1 + 0.1 N``, biases ``0.1 N`` — so norms and biases
are not identities the check could miss.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

__all__ = ["base_key", "leaf", "leaf_key", "leaf_from_key", "leaf_names",
           "make_params"]


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (``--seed`` may exceed 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    for word in ((seed >> 31) & 0x7FFFFFFF, (seed >> 62) & 0x7FFFFFFF,
                 1 if seed < 0 else 0):
        key = jax.random.fold_in(key, word)
    return key


def leaf_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def leaf(key: jax.Array, name: str, shape, dtype=jnp.bfloat16) -> jax.Array:
    """The leaf ``name`` of the model made from ``key`` (traceable)."""
    return leaf_from_key(leaf_key(key, name), name, shape, dtype)


def leaf_from_key(k: jax.Array, name: str, shape,
                  dtype=jnp.bfloat16) -> jax.Array:
    """The leaf ``name`` from its own key ``leaf_key(key, name)``; the scale
    depends only on the name's last parts, so one program serves every
    layer."""
    z = jax.random.normal(k, shape, jnp.float32)
    last = name.rsplit("/", 2)
    if name.endswith("/b"):
        x = 0.1 * z
    elif len(shape) == 1:                       # norm gain
        x = 1.0 + 0.1 * z
    elif last[0] in ("embed", "lm_head"):       # (vocab, width) tables
        x = z * shape[-1] ** -0.5
    else:                                       # (in, out) matrices
        x = z * shape[-2] ** -0.5
    return x.astype(dtype)


def leaf_names(tree) -> list:
    """Slash-joined key paths of a parameter tree, in flatten order."""
    paths, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, _ in paths:
        parts = []
        for p in path:
            parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
        out.append("/".join(parts))
    return out


def make_params(shapes, seed: int, shardings=None):
    """Fill the program's parameter tree (``jax.eval_shape`` of its init)
    with the benchmark's weights in one jitted call on the device."""
    names = leaf_names(shapes)
    flat, treedef = jax.tree_util.tree_flatten(shapes)

    def build(key):
        return jax.tree_util.tree_unflatten(
            treedef, [leaf(key, n, s.shape, s.dtype)
                      for n, s in zip(names, flat)])

    fn = jax.jit(build) if shardings is None else jax.jit(
        build, out_shardings=shardings)
    return fn(base_key(seed))
