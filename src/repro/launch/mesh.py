"""Mesh construction (functions, not module constants — importing this
module never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh",
           "make_kv_mesh"]


def make_mesh(shape, axes, *, devices=None):
    """The repo's one mesh constructor: ``jax.make_mesh`` with every axis
    ``Auto``-typed. The programs here place arrays with NamedShardings and
    sharding constraints and let GSPMD propagate; Explicit axes (the
    ``jax.make_mesh`` default) would instead type every array by its
    sharding and refuse plain indexing of sharded values."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 4):
    """Small mesh over host devices (tests/examples)."""
    n = len(jax.devices())
    while data * model > n and data > 1:
        data //= 2
    while data * model > n and model > 1:
        model //= 2
    return make_mesh((data, model), ("data", "model"))


def make_kv_mesh(kv: int = 2, data: int = 2, model: int = 4):
    """Host mesh with a leading ``kv`` axis for sequence-sharded KV pools
    (DESIGN.md §Sequence-sharded pools). The kv extent is honored exactly
    (it sets the pool capacity split the engine is sized around); data and
    model shrink to fit the available devices."""
    n = len(jax.devices())
    if kv > n:
        raise ValueError(
            f"--shard-pools {kv} needs at least {kv} devices, have {n}")
    while kv * data * model > n and data > 1:
        data //= 2
    while kv * data * model > n and model > 1:
        model //= 2
    return make_mesh((kv, data, model), ("kv", "data", "model"))
