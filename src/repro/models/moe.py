"""Mixture-of-Experts layer: top-k routing, sort-based capacity dispatch,
expert-parallel weights, TP row-reduction compressed per the paper.

Routing is *grouped*: tokens are split into G groups aligned with the data
shards, so all routing math (top-k, sort, position-in-expert) is
embarrassingly parallel per group and GSPMD never needs a cross-shard sort.
The expert einsum reshards group-sharded activations to expert-sharded
weights — XLA inserts the expert-parallel all-to-all automatically.

Dispatch is sort-based (Megablocks-style with fixed capacity): tokens sorted
by expert id, position-within-expert from per-group segment starts, scattered
into an (E, C, d) buffer with an overflow slot — no (T, E, C) one-hot tensor
is ever materialized (the GShard formulation is quadratically wasteful at
1M-token prefill).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.collectives import psum_maybe_compressed
from repro.core.tp import TPContext, constrain
from repro.models.common import Initializer
from repro.models.mlp import init_mlp, mlp

__all__ = ["init_moe", "moe", "moe_specs"]


def init_moe(init: Initializer, name: str, cfg: ModelConfig):
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    p = {
        "router": {"w": init.linear(f"{name}/router", (d, E), scale=d**-0.5)},
        "up": {"w": init.linear(f"{name}/up", (E, d, f))},
        "gate": {"w": init.linear(f"{name}/gate", (E, d, f))},
        "down": {"w": init.linear(f"{name}/down", (E, f, d))},
    }
    for i in range(cfg.n_shared_experts):
        p[f"shared{i}"] = init_mlp(init, f"{name}/shared{i}", cfg)
    return p


def _num_groups(ctx: TPContext, batch: int) -> int:
    g = ctx.dp_size
    while batch % g != 0:
        g -= 1
    return max(g, 1)


def _capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    c = int(cfg.capacity_factor * tokens_per_group * cfg.top_k / cfg.n_experts)
    return max(1, c)


def _expert_ffn(ctx: TPContext, params, expert_in: jnp.ndarray,
                cfg: ModelConfig) -> jnp.ndarray:
    """Expert FFN on dispatched tokens (G, E, C, d) -> (G, E, C, d).

    Expert-parallel island (the production path, when E divides the DP
    degree and G == dp): manual over {data..., model}; an explicit
    all-to-all exchanges token groups <-> expert shards, each device runs
    its E/dp experts with d_ff TP-sharded, and the down-projection partial
    sums are reduced with the paper's compressed psum. The all-to-alls
    themselves are compressible via policy.compress_all_to_all
    (beyond-paper extension).

    Fallback (E not divisible by dp, e.g. mixtral's 8 experts on a 16-way
    data axis; or no mesh): GSPMD-auto einsums with 2-D-sharded expert
    weights — correct, uncompressed on the expert path (DESIGN.md
    §Arch-applicability).
    """
    G, E, C, d = expert_in.shape
    dp = ctx.dp_size
    use_island = (
        ctx.tp and ctx.data_axes and E % dp == 0 and G == dp and dp > 1
    )
    if not use_island:
        h = jnp.einsum("gecd,edf->gecf", expert_in,
                       params["up"]["w"].astype(expert_in.dtype))
        g_ = jnp.einsum("gecd,edf->gecf", expert_in,
                        params["gate"]["w"].astype(expert_in.dtype))
        h = jax.nn.silu(g_) * h
        if ctx.tp:
            h = constrain(ctx, h, ctx.batch, None, None, ctx.axis)
        return jnp.einsum("gecf,efd->gecd", h,
                          params["down"]["w"].astype(h.dtype))

    policy, axis = ctx.policy, ctx.axis
    tp_size = ctx.tp_size
    data_axes = ctx.data_axes
    a2a_axis = data_axes[-1] if len(data_axes) == 1 else data_axes
    El = E // dp
    spec = policy.spec if (policy.enabled and policy.compress_all_to_all) else None

    def _a2a(t):
        if spec is not None:
            from repro.core.collectives import compressed_all_to_all

            return compressed_all_to_all(t, a2a_axis, spec, split_axis=0,
                                         concat_axis=0,
                                         use_pallas=policy.use_pallas)
        return jax.lax.all_to_all(t, a2a_axis, split_axis=0, concat_axis=0)

    def island(x_l, wu, wg, wd):
        # x_l (1, E, C, d) -> (dp, E/dp, C, d): groups <-> experts
        x_l = x_l.reshape(dp, El, C, d)
        x_l = _a2a(x_l)                       # (dp=src group, El, C, d)
        h = jnp.einsum("gecd,edf->gecf", x_l, wu.astype(x_l.dtype))
        g_ = jnp.einsum("gecd,edf->gecf", x_l, wg.astype(x_l.dtype))
        h = jax.nn.silu(g_) * h
        part = jnp.einsum("gecf,efd->gecd", h, wd.astype(h.dtype))
        out = psum_maybe_compressed(part, axis, policy, n_tokens=dp * El * C,
                                    axis_size=tp_size)
        out = _a2a(out)                       # back: (dp, El, C, d)
        return out.reshape(1, E, C, d)

    e_entry = data_axes if len(data_axes) > 1 else data_axes[0]
    return jax.shard_map(
        island,
        mesh=ctx.mesh,
        in_specs=(
            P(e_entry, None, None, None),     # expert_in: G over data
            P(e_entry, None, axis),           # up   (E, d, f)
            P(e_entry, None, axis),           # gate
            P(e_entry, axis, None),           # down (E, f, d)
        ),
        out_specs=P(e_entry, None, None, None),
        axis_names={axis, *data_axes},
        check_vma=False,
    )(expert_in, params["up"]["w"], params["gate"]["w"], params["down"]["w"])


def moe(
    ctx: TPContext, params, x: jnp.ndarray, cfg: ModelConfig
) -> Tuple[jnp.ndarray, dict]:
    """x (B, S, d) -> (out (B, S, d), aux losses)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    G = _num_groups(ctx, B)
    Tg = (B // G) * S
    C = _capacity(cfg, Tg)

    xg = x.reshape(G, Tg, d)
    xg = constrain(ctx, xg, ctx.batch, None, None)

    # --- routing (per group, fp32) ---
    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        params["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, topk_idx = jax.lax.top_k(probs, k)          # (G, Tg, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance aux loss + router z-loss
    me = jnp.mean(jax.nn.one_hot(topk_idx, E, dtype=jnp.float32), axis=(1, 2))
    ce = jnp.mean(probs, axis=1)
    aux = {
        "load_balance": E * jnp.mean(jnp.sum(me * ce, axis=-1)),
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
    }

    # --- tiny-token dense path (long-context decode: B*S <= 64) ---
    # scatter-free: computes every expert on every token and mixes with the
    # routing weights. Avoids SPMD-partitioned scatter(set) ops entirely —
    # XLA-CPU aborts cloning the all-reduce(copy) they partition into — and
    # is compute-cheaper than the dispatch machinery at this scale anyway.
    if B * S <= 64:
        wmix = (gates[..., None] * jax.nn.one_hot(topk_idx, E, dtype=gates.dtype)
                ).sum(-2).astype(x.dtype)               # (G, Tg, E)
        h = jnp.einsum("gtd,edf->gtef", xg, params["up"]["w"].astype(x.dtype))
        g_ = jnp.einsum("gtd,edf->gtef", xg, params["gate"]["w"].astype(x.dtype))
        eo = jnp.einsum("gtef,efd->gted", jax.nn.silu(g_) * h,
                        params["down"]["w"].astype(x.dtype))
        out = jnp.einsum("gted,gte->gtd", eo, wmix).reshape(B, S, d)
        for i in range(cfg.n_shared_experts):
            out = out + mlp(ctx, params[f"shared{i}"], x, cfg)
        return out, aux

    # --- sort-based dispatch (per group, static shapes) ---
    fe = topk_idx.reshape(G, Tg * k)                   # expert id per slot
    fg = gates.reshape(G, Tg * k).astype(x.dtype)
    order = jnp.argsort(fe, axis=-1, stable=True)
    se = jnp.take_along_axis(fe, order, axis=-1)       # sorted expert ids
    st = order // k                                    # source token
    sg = jnp.take_along_axis(fg, order, axis=-1)
    starts = jax.vmap(lambda row: jnp.searchsorted(row, jnp.arange(E)))(se)
    pos = jnp.arange(Tg * k)[None, :] - jnp.take_along_axis(starts, se, axis=-1)
    keep = pos < C
    dest = jnp.where(keep, se * C + pos, E * C)        # E*C = overflow slot

    def scatter_group(xg_g, st_g, dest_g):
        buf = jnp.zeros((E * C + 1, d), xg_g.dtype)
        return buf.at[dest_g].set(xg_g[st_g])

    buf = jax.vmap(scatter_group)(xg, st, dest)        # (G, E*C+1, d)
    expert_in = buf[:, : E * C].reshape(G, E, C, d)
    expert_in = constrain(ctx, expert_in, ctx.batch, None, None, None)

    expert_out = _expert_ffn(ctx, params, expert_in, cfg)  # (G, E, C, d)

    # --- combine back to tokens ---
    flat = expert_out.reshape(G, E * C, d)
    flat = jnp.concatenate([flat, jnp.zeros((G, 1, d), flat.dtype)], axis=1)

    def gather_group(flat_g, dest_g, sg_g, st_g):
        contrib = flat_g[dest_g] * sg_g[:, None]       # (Tg*k, d)
        return jnp.zeros((Tg, d), flat_g.dtype).at[st_g].add(contrib)

    out = jax.vmap(gather_group)(flat, dest, sg, st).reshape(B, S, d)

    for i in range(cfg.n_shared_experts):
        out = out + mlp(ctx, params[f"shared{i}"], x, cfg)
    return out, aux


def moe_specs(cfg: ModelConfig, ctx: TPContext):
    from repro.models.mlp import mlp_specs

    a = ctx.axis if ctx.tp else None
    dp = ctx.dp_size
    if ctx.data_axes and cfg.n_experts % dp == 0:
        e = ctx.batch  # expert-parallel over data axes (island path)
        p = {
            "up": {"w": P(e, None, a)},
            "gate": {"w": P(e, None, a)},
            "down": {"w": P(e, a, None)},
        }
    else:
        # E doesn't divide dp (mixtral 8e on 16-way data): 2-D shard the
        # per-expert matrices instead (auto fallback path)
        d0 = ctx.data_axes[0] if ctx.data_axes else None  # keep: mixtral experts must 2-D shard even in serve (memory)
        p = {
            "up": {"w": P(None, d0, a)},
            "gate": {"w": P(None, d0, a)},
            "down": {"w": P(None, a, d0)},
        }
    p["router"] = {"w": P(None, None)}
    for i in range(cfg.n_shared_experts):
        p[f"shared{i}"] = mlp_specs(cfg, ctx)
    return p
