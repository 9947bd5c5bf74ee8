"""Sharding policy: build the TPContext + input/batch shardings for a given
(mesh, input shape).

Rules (DESIGN.md §3):
  weights      in-dim -> data, out-dim/heads/d_ff -> model (2-D, ZeRO-flavor)
  experts      expert dim -> data axes
  activations  batch -> (pod?, data), features/heads -> model
  long_500k    batch=1: batch unsharded, KV-cache seq dim -> data
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape
from repro.core.policy import CompressionPolicy, NO_COMPRESSION
from repro.core.tp import TPContext

__all__ = ["make_context", "input_shardings", "param_shardings",
           "init_params_sharded"]


def make_context(
    mesh: Optional[jax.sharding.Mesh],
    shape: Optional[InputShape] = None,
    *,
    policy: CompressionPolicy = NO_COMPRESSION,
    scan_layers: bool = False,
    remat: bool = False,
    fuse_mlp_island: bool = False,
    kv_axis: Optional[str] = None,
) -> TPContext:
    if mesh is None:
        return TPContext(mesh=None, policy=policy)
    axes = mesh.axis_names
    if kv_axis is not None and kv_axis not in axes:
        raise ValueError(
            f"kv_axis {kv_axis!r} is not a mesh axis (have {axes}); build "
            f"the mesh with make_kv_mesh or drop the pool sharding")
    data_axes = tuple(a for a in ("pod", "data") if a in axes)
    seq_axis = None
    if shape is not None and shape.global_batch < mesh.shape.get("data", 1):
        # batch too small to shard (long_500k): unshard batch, shard cache seq
        data_axes = ()
        seq_axis = "data"
    return TPContext(
        mesh=mesh,
        axis="model",
        data_axes=data_axes,
        seq_axis=seq_axis,
        kv_axis=kv_axis,
        policy=policy,
        scan_layers=scan_layers,
        remat=remat,
        fuse_mlp_island=fuse_mlp_island,
        # ZeRO weight sharding only for training: for serving, data-sharded
        # weight in-dims make XLA gather *activations* over data for the
        # column matmuls (measured: 384 GiB of bogus all-gather per prefill)
        zero_weights=(shape is None or shape.kind == "train"),
    )


def resolve_specs(shapes_tree, specs_tree, mesh):
    """Drop axis placements that don't divide the dim evenly — jit input
    shardings (unlike internal constraints) require exact divisibility.
    E.g. whisper's vocab 51865 can't shard 16 ways; 8 KV heads can't take a
    16-way model axis."""

    def resolve_one(sds, spec):
        new = []
        for dim, entry in zip(sds.shape, tuple(spec) + (None,) * (len(sds.shape) - len(spec))):
            if entry is None:
                new.append(None)
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            new.append(entry if dim % size == 0 else None)
        return P(*new)

    return jax.tree.map(
        resolve_one, shapes_tree, specs_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


def param_shardings(model, ctx: TPContext):
    """NamedSharding pytree for ``model``'s parameters from
    ``Model.param_specs`` (placements that don't divide a dim dropped);
    None without a mesh."""
    if ctx.mesh is None:
        return None
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    specs = resolve_specs(shapes, model.param_specs(ctx), ctx.mesh)
    return jax.tree.map(lambda s: NamedSharding(ctx.mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def init_params_sharded(model, ctx: TPContext, key: jax.Array):
    """Random parameters initialised under jit straight into their
    shardings: each device computes and holds only its own shards, so no
    device ever holds the whole model."""
    return jax.jit(model.init_params,
                   out_shardings=param_shardings(model, ctx))(key)


def input_shardings(ctx: TPContext, specs: Dict) -> Dict:
    """NamedSharding-annotated ShapeDtypeStructs for model inputs."""
    if ctx.mesh is None:
        return specs
    out = {}
    for k, sds in specs.items():
        pspec = P(ctx.batch, *([None] * (len(sds.shape) - 1)))
        out[k] = jax.ShapeDtypeStruct(
            sds.shape, sds.dtype, sharding=NamedSharding(ctx.mesh, pspec)
        )
    return out
