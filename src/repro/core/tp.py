"""Tensor-parallel linear layers as shard_map islands inside a GSPMD program.

The model code is written against ``TPContext``: when a mesh with the TP axis
is present, row-parallel layers become shard_map islands (manual ONLY over the
TP axis — everything else, batch/expert/pod sharding, stays GSPMD-auto) whose
reduction is the paper's compressed psum. When no mesh is given (CPU smoke
tests, single device), the same functions degrade to plain local matmuls.

Only *flattened feature dims* are sharded inside islands, so head-count
divisibility never constrains the island (GSPMD pads heads outside).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.collectives import masked_owner_psum, psum_maybe_compressed
from repro.core.policy import CompressionPolicy, NO_COMPRESSION

__all__ = [
    "TPContext", "row_linear", "column_linear", "fused_mlp", "constrain",
    "pool_exchange", "pool_scatter", "pool_block_write", "pool_block_fill",
    "pool_block_copy",
]


@dataclasses.dataclass(frozen=True)
class TPContext:
    """Everything model code needs to know about distribution."""

    mesh: Optional[jax.sharding.Mesh] = None
    axis: str = "model"                       # TP axis name
    data_axes: tuple = ("data",)              # batch axes (may include "pod");
                                              # () => batch not sharded
    seq_axis: Optional[str] = None            # shard KV-cache sequence dim
                                              # (static prefill path only)
    kv_axis: Optional[str] = None             # shard paged-pool BLOCK dim:
                                              # each device owns
                                              # capacity/kv_shards pool blocks
                                              # (DESIGN.md §Sequence-sharded
                                              # pools)
    policy: CompressionPolicy = NO_COMPRESSION
    fuse_mlp_island: bool = False             # perf: column+row in one island
    scan_layers: bool = False                 # lax.scan over repeated layers
    remat: bool = False                       # per-layer activation checkpoint
    zero_weights: bool = True                 # ZeRO: shard weight in-dims over
                                              # data (train); False => weights
                                              # replicated over data (serve)
    simulate_tp: int = 0                      # single-device TP emulation:
                                              # split row-parallel contractions
                                              # into N quantized partial sums
                                              # (quality evaluation, paper §5.1
                                              # and Table 5 "parallelism")

    @property
    def tp(self) -> bool:
        return self.mesh is not None and self.axis in self.mesh.axis_names

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.axis] if self.tp else 1

    @property
    def kv_shards(self) -> int:
        """Number of shards the paged pools' block dim is split into."""
        if self.mesh is not None and self.kv_axis in self.mesh.axis_names:
            return self.mesh.shape[self.kv_axis]
        return 1

    @property
    def kv_sharded(self) -> bool:
        return self.kv_shards > 1

    @property
    def batch(self):
        """PartitionSpec entry for a batch dimension."""
        return tuple(self.data_axes) if self.data_axes else None

    @property
    def dp_size(self) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for a in self.data_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def wdata(self):
        """Data axis for weight secondary sharding (ZeRO) — None for serve."""
        if self.zero_weights and self.data_axes:
            return self.data_axes[0]
        return None

    def sharding(self, *spec) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(*spec))

    def without_compression(self) -> "TPContext":
        """The dense gate variant of this context: identical distribution,
        uncompressed collectives. The serving engine compiles the mixed
        program once per gate variant (this ctx and the compressed one) and
        dispatches per step on the batch's real composition."""
        if not self.policy.enabled:
            return self
        return dataclasses.replace(self, policy=NO_COMPRESSION)


def constrain(ctx: TPContext, x: jnp.ndarray, *spec) -> jnp.ndarray:
    """with_sharding_constraint that is a no-op without a mesh and silently
    drops placements that don't divide the dim (e.g. 28 heads on a 16-way
    axis) — sharding is a performance hint, never a correctness requirement.
    """
    if ctx.mesh is None:
        return x
    resolved = []
    for dim, entry in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec))):
        if entry is None:
            resolved.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for a in axes:
            size *= ctx.mesh.shape[a]
        resolved.append(entry if dim % size == 0 else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(*resolved))
    )


def _leading_none(ndim: int, last) -> P:
    return P(*([None] * (ndim - 1)), last)


def island_axes(ctx: TPContext, batch_dim: int):
    """(batch spec entry for dim 0, manual axis set) for a shard_map island.

    Islands are manual over the TP axis AND the batch data axes: with
    partial-manual shard_map, GSPMD *replicates* auto axes inside the body
    (verified empirically — a (B,...) input arrives un-sharded over data),
    which would multiply the collective payload by the data-parallel degree.
    Manual-everything keeps the batch sharded; the batch entry is dropped
    when the dim doesn't divide (then data axes stay out of the island).
    """
    entry = None
    # manual over EVERY mesh axis: partial-manual islands make SPMD emit
    # replication-enforcing bf16 all-reduce(copy) ops on the idle axes,
    # which XLA-CPU's AllReducePromotion pass aborts on (and which would be
    # wasted traffic on TPU too). Unmentioned manual axes = replicated.
    names = set(ctx.mesh.axis_names) if ctx.mesh is not None else {ctx.axis}
    if ctx.data_axes and batch_dim % ctx.dp_size == 0:
        entry = ctx.batch
    return entry, names


def column_linear(
    ctx: TPContext,
    x: jnp.ndarray,
    w: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """y = x @ w, w (Fin, Fout) sharded Fout over the TP axis (GSPMD-auto;
    no collective needed). Output's last dim is TP-sharded."""
    y = jnp.einsum("...i,io->...o", x, w.astype(x.dtype))
    if bias is not None:
        y = y + bias.astype(y.dtype)
    if ctx.tp:
        # NOTE: the batch entry matters — a None entry in a sharding
        # constraint means *replicate that dim* (Shardy closed-dim
        # semantics), which would force a full-batch all-gather here
        y = constrain(ctx, y, ctx.batch, *([None] * (y.ndim - 2)), ctx.axis)
    return y


def row_linear(
    ctx: TPContext,
    x: jnp.ndarray,
    w: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    *,
    n_tokens: Optional[int] = None,
) -> jnp.ndarray:
    """y = sum_shards(x_shard @ w_shard): the row-parallel layer whose
    reduction the paper compresses.

    x: (..., Fin) with Fin TP-sharded; w: (Fin, Fout) with Fin TP-sharded.
    Output replicated over the TP axis. Bias added once (post-reduction).
    """
    if not ctx.tp:
        n = ctx.simulate_tp
        if (n > 1 and ctx.policy.enabled and ctx.policy.compress_tp_reduce
                and x.shape[-1] % n == 0
                and w.shape[-1] % ctx.policy.spec.block_size == 0):
            from repro.core.mx import fake_quantize

            fin = x.shape[-1]
            xs = x.reshape(*x.shape[:-1], n, fin // n)
            ws = w.reshape(n, fin // n, w.shape[-1]).astype(x.dtype)
            parts = jnp.einsum("...nc,nco->n...o", xs, ws)
            parts = fake_quantize(parts, ctx.policy.spec)
            y = jnp.sum(parts.astype(jnp.float32), axis=0)
            if ctx.policy.variant == "two_phase":
                # two-phase requantizes the reduced result once more
                y = fake_quantize(y.astype(x.dtype), ctx.policy.spec)
            y = y.astype(x.dtype)
        else:
            y = jnp.einsum("...i,io->...o", x, w.astype(x.dtype))
        return y if bias is None else y + bias.astype(y.dtype)

    if n_tokens is None:
        n_tokens = 1
        for d in x.shape[:-1]:
            n_tokens *= int(d)

    policy = ctx.policy
    axis = ctx.axis
    tp_size = ctx.tp_size
    b_entry, names = island_axes(ctx, x.shape[0])
    n_tokens //= max(1, ctx.dp_size if b_entry is not None else 1)

    def island(x_local, w_local):
        part = jnp.einsum("...i,io->...o", x_local, w_local.astype(x_local.dtype))
        return psum_maybe_compressed(part, axis, policy, n_tokens=n_tokens,
                                     axis_size=tp_size)

    mids = [None] * (x.ndim - 2)
    y = jax.shard_map(
        island,
        mesh=ctx.mesh,
        in_specs=(P(b_entry, *mids, axis), P(axis, None)),
        out_specs=P(b_entry, *mids, None),
        axis_names=names,
        check_vma=False,
    )(x, w)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def fused_mlp(
    ctx: TPContext,
    x: jnp.ndarray,
    w_gate: Optional[jnp.ndarray],
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    *,
    act=jax.nn.silu,
    n_tokens: Optional[int] = None,
) -> jnp.ndarray:
    """Column(gate,up) + activation + row(down) in ONE shard_map island.

    Avoids the GSPMD boundary reshard between column and row halves — a perf
    lever measured in EXPERIMENTS.md §Perf. Semantics identical to
    column_linear + row_linear composition.
    """
    if not ctx.tp:
        h = jnp.einsum("...i,io->...o", x, w_up.astype(x.dtype))
        if w_gate is not None:
            h = act(jnp.einsum("...i,io->...o", x, w_gate.astype(x.dtype))) * h
        else:
            h = act(h)
        return jnp.einsum("...i,io->...o", h, w_down.astype(x.dtype))

    if n_tokens is None:
        n_tokens = 1
        for d in x.shape[:-1]:
            n_tokens *= int(d)

    policy = ctx.policy
    axis = ctx.axis
    tp_size = ctx.tp_size
    has_gate = w_gate is not None
    b_entry, names = island_axes(ctx, x.shape[0])
    n_tokens //= max(1, ctx.dp_size if b_entry is not None else 1)

    def island(x_rep, *ws):
        if has_gate:
            wg, wu, wd = ws
        else:
            (wu, wd), wg = ws, None
        h = jnp.einsum("...i,io->...o", x_rep, wu.astype(x_rep.dtype))
        if wg is not None:
            g = jnp.einsum("...i,io->...o", x_rep, wg.astype(x_rep.dtype))
            h = act(g) * h
        else:
            h = act(h)
        part = jnp.einsum("...i,io->...o", h, wd.astype(h.dtype))
        return psum_maybe_compressed(part, axis, policy, n_tokens=n_tokens,
                                     axis_size=tp_size)

    w_specs = (P(None, axis),) * (2 if has_gate else 1) + (P(axis, None),)
    args = ((w_gate, w_up, w_down) if has_gate else (w_up, w_down))
    mids = [None] * (x.ndim - 2)
    return jax.shard_map(
        island,
        mesh=ctx.mesh,
        in_specs=(P(b_entry, *mids, None), *w_specs),
        out_specs=P(b_entry, *mids, None),
        axis_names=names,
        check_vma=False,
    )(x, *args)


# --------------------------------------------------------------------------
# Sequence-sharded paged pools (DESIGN.md §Sequence-sharded pools).
#
# The pools keep their GLOBAL logical shape (n_blocks, block, width); only
# the physical layout splits the block dim contiguously over ctx.kv_axis.
# Ownership is a pure function of the global block id:
#
#     per_shard = n_blocks // kv_shards
#     shard_of(g) = g // per_shard          local_of(g) = g % per_shard
#
# so pool row == global id and kv_shards == 1 degrades to the replicated
# layout byte-for-byte. Every island below is manual over EVERY mesh axis
# (see island_axes: partial-manual islands abort XLA-CPU), reads/writes its
# (per_shard, block, width_local) slab, and communicates ONLY over the kv
# axis — table-named blocks via masked_owner_psum on the read side, nothing
# at all on the write side (non-owners drop their scatter rows).
# --------------------------------------------------------------------------


def _kv_geometry(ctx: TPContext, pool: jnp.ndarray):
    """(kv axis name, per-shard block count) for a sharded pool array."""
    assert ctx.kv_sharded, "pool islands require a kv-sharded context"
    n_blocks = pool.shape[0]
    assert n_blocks % ctx.kv_shards == 0, (
        f"pool capacity {n_blocks} does not divide over {ctx.kv_shards} "
        "kv shards (the engine rounds capacity up at construction)"
    )
    return ctx.kv_axis, n_blocks // ctx.kv_shards


def _m_entry(ctx: TPContext, dim: int) -> Optional[str]:
    """TP-axis spec entry for a feature dim — None when it doesn't divide
    (mirrors ``constrain``'s silent drop; wire scales dims are often tiny)."""
    if ctx.tp and dim % ctx.tp_size == 0:
        return ctx.axis
    return None


def pool_exchange(ctx: TPContext, pools, tables: jnp.ndarray):
    """Gather the table-named blocks of each pool array into a kv-replicated
    "virtual pool" laid out in table order.

    pools: sequence of (n_blocks, block, width) arrays (dense kv, or wire
    payload/scales planes). tables: (R, nb) int32 global block ids.
    Returns a list of (R*nb, block, width) arrays with
    ``out[i][r*nb + j] == pools[i][tables[r, j]]`` bit-for-bit on every
    shard. Wire volume per array is len(tables) blocks — bounded by resident
    context, never pool capacity (the full-pool all-gather the ``pool-reshard``
    audit rule forbids).
    """
    kv, per_shard = _kv_geometry(ctx, pools[0])
    names = set(ctx.mesh.axis_names)
    m_entries = [_m_entry(ctx, p.shape[-1]) for p in pools]

    def island(t, *slabs):
        me = jax.lax.axis_index(kv)
        flat = t.reshape(-1)
        own = ((flat // per_shard) == me)[:, None, None]
        local = flat % per_shard
        return tuple(
            masked_owner_psum(slab[local], own, kv) for slab in slabs
        )

    return list(jax.shard_map(
        island,
        mesh=ctx.mesh,
        in_specs=(P(None, None),) + tuple(P(kv, None, m) for m in m_entries),
        out_specs=tuple(P(None, None, m) for m in m_entries),
        axis_names=names,
        check_vma=False,
    )(tables, *pools))


def _drop_row(kv: str, per_shard: int, blk: jnp.ndarray) -> jnp.ndarray:
    """Local slab row for owned global ids; ``per_shard`` (out of bounds, so
    a mode="drop" scatter discards it) for everything this shard doesn't own."""
    me = jax.lax.axis_index(kv)
    return jnp.where((blk // per_shard) == me, blk % per_shard, per_shard)


def pool_scatter(ctx: TPContext, pools_vals, blk: jnp.ndarray,
                 offs: jnp.ndarray):
    """Per-position append into sharded pools: each (pool, vals) pair writes
    ``vals[i]`` (shape (N, width)) at (blk[i], offs[i]). Communication-free:
    every shard scatters only the rows it owns and drops the rest."""
    kv, per_shard = _kv_geometry(ctx, pools_vals[0][0])
    names = set(ctx.mesh.axis_names)
    m_entries = [_m_entry(ctx, p.shape[-1]) for p, _ in pools_vals]
    k = len(pools_vals)

    def island(b, o, *arrs):
        lb = _drop_row(kv, per_shard, b)
        return tuple(
            slab.at[lb, o].set(v, mode="drop")
            for slab, v in zip(arrs[:k], arrs[k:])
        )

    pool_specs = tuple(P(kv, None, m) for m in m_entries)
    val_specs = tuple(P(None, m) for m in m_entries)
    flat = [p for p, _ in pools_vals] + [v for _, v in pools_vals]
    return list(jax.shard_map(
        island,
        mesh=ctx.mesh,
        in_specs=(P(None), P(None)) + pool_specs + val_specs,
        out_specs=pool_specs,
        axis_names=names,
        check_vma=False,
    )(blk, offs, *flat))


def pool_block_write(ctx: TPContext, pools_vals, block_ids: jnp.ndarray):
    """Whole-block write (prefix-cache insert): each (pool, vals) pair writes
    ``vals`` (shape (n, block, width)) at rows ``block_ids``. Communication-
    free, same drop discipline as ``pool_scatter``."""
    kv, per_shard = _kv_geometry(ctx, pools_vals[0][0])
    names = set(ctx.mesh.axis_names)
    m_entries = [_m_entry(ctx, p.shape[-1]) for p, _ in pools_vals]
    k = len(pools_vals)

    def island(b, *arrs):
        lb = _drop_row(kv, per_shard, b)
        return tuple(
            slab.at[lb].set(v, mode="drop")
            for slab, v in zip(arrs[:k], arrs[k:])
        )

    pool_specs = tuple(P(kv, None, m) for m in m_entries)
    val_specs = tuple(P(None, None, m) for m in m_entries)
    flat = [p for p, _ in pools_vals] + [v for _, v in pools_vals]
    return list(jax.shard_map(
        island,
        mesh=ctx.mesh,
        in_specs=(P(None),) + pool_specs + val_specs,
        out_specs=pool_specs,
        axis_names=names,
        check_vma=False,
    )(block_ids, *flat))


def pool_block_fill(ctx: TPContext, pools_fills, block: jnp.ndarray):
    """Fill one block (scalar global id) of each pool array with a constant
    (fault injection: poisoned wire scales / NaN dense blocks). pools_fills:
    sequence of (pool, python_scalar) pairs."""
    kv, per_shard = _kv_geometry(ctx, pools_fills[0][0])
    names = set(ctx.mesh.axis_names)
    m_entries = [_m_entry(ctx, p.shape[-1]) for p, _ in pools_fills]
    fills = [f for _, f in pools_fills]

    def island(b, *slabs):
        lb = _drop_row(kv, per_shard, b)
        return tuple(
            slab.at[lb].set(jnp.full(slab.shape[1:], f, slab.dtype),
                            mode="drop")
            for slab, f in zip(slabs, fills)
        )

    pool_specs = tuple(P(kv, None, m) for m in m_entries)
    return list(jax.shard_map(
        island,
        mesh=ctx.mesh,
        in_specs=(P(),) + pool_specs,
        out_specs=pool_specs,
        axis_names=names,
        check_vma=False,
    )(block, *[p for p, _ in pools_fills]))


def pool_block_copy(ctx: TPContext, pools, src: jnp.ndarray,
                    dst: jnp.ndarray):
    """Copy block ``src`` to block ``dst`` (copy-on-write fork) across
    shards: the owner of ``src`` broadcasts one block over the kv axis
    (bit-exact masked psum), the owner of ``dst`` writes it, everyone else
    drops. One block of wire per pool array."""
    kv, per_shard = _kv_geometry(ctx, pools[0])
    names = set(ctx.mesh.axis_names)
    m_entries = [_m_entry(ctx, p.shape[-1]) for p in pools]

    def island(s, d, *slabs):
        me = jax.lax.axis_index(kv)
        src_own = (s // per_shard) == me
        ld = jnp.where((d // per_shard) == me, d % per_shard, per_shard)
        outs = []
        for slab in slabs:
            data = masked_owner_psum(slab[s % per_shard], src_own, kv)
            outs.append(slab.at[ld].set(data, mode="drop"))
        return tuple(outs)

    pool_specs = tuple(P(kv, None, m) for m in m_entries)
    return list(jax.shard_map(
        island,
        mesh=ctx.mesh,
        in_specs=(P(), P()) + pool_specs,
        out_specs=pool_specs,
        axis_names=names,
        check_vma=False,
    )(src, dst, *pools))
