"""GQA attention with RoPE, qk-norm, QKV bias, sliding windows, KV cache —
TP-sharded with the paper's compressed reduction on the output projection.

Design notes (production sharding, see DESIGN.md):

* KV caches are stored FLAT as (B, S, kv_dim = n_kv_heads*head_dim). kv_dim
  is divisible by the 16-way model axis for every assigned arch (head
  *counts* often are not: qwen2 has 4 KV heads), and the flat layout is
  exactly how the column-parallel K/V projections produce the values — no
  resharding on the cache write path. GSPMD represents the reshape-to-heads
  sharding natively as a (kv, hd) 2-D tiling.

* Scores are never materialized at (S, T): prefill/training attention runs
  q-CHUNKED (lax.scan over query blocks, masks built per block), bounding
  the transient to (B, chunk, H, T) — the pure-JAX analogue of flash
  attention's blocking, chosen for the TPU dry-run memory envelope.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import mx
from repro.core.formats import KVCacheSpec
from repro.core.mx import MXCompressed
from repro.core.tp import TPContext, column_linear, constrain, row_linear
from repro.models.common import Initializer, apply_rope, init_linear, make_rope, rms_norm

__all__ = ["init_attention", "KVCache", "init_cache", "attention",
           "attention_specs", "paged_attention_decode", "paged_attention_chunk",
           "paged_attention_mixed", "quantize_kv_pages", "READ_TILE",
           "mixed_read_tiles", "read_tiles_held", "read_tiles_used"]

NEG_INF = -1e30
_Q_CHUNK = 1024
# named scope of the mixed step's paged attention (history read, attention,
# pool append): what a device trace credits that work to
PAGED_ATTENTION_SCOPE = "paged_attention"
# query rows per tile of the mixed step's jnp history read: the f32 sublane
# count, so one tile's scores against a history fill whole vector registers
READ_TILE = 8


def mixed_read_tiles(token_budget: int, n_slots: int) -> int:
    """Static tile count of the mixed step's jnp history read: the most
    tiles that any packing of ``token_budget`` rows into at most ``n_slots``
    one-slot segments fills. A segment of n rows fills ceil(n / L) tiles, and
    sum_s ceil(n_s / L) <= (sum_s n_s + n_slots * (L - 1)) // L; no tile is
    empty, so it never exceeds the rows either."""
    return min(token_budget,
               (token_budget + n_slots * (READ_TILE - 1)) // READ_TILE)


def _pallas_read(cache_spec) -> bool:
    """Whether the Pallas kernel serves the mixed step's history read (each
    row walks its own slot's table) instead of the tiled jnp gather."""
    return cache_spec is not None and cache_spec.use_pallas


def read_tiles_held(token_budget: int, n_slots: int, cache_spec) -> int:
    """Tiles the mixed step's history read holds each step under
    ``cache_spec``: ``mixed_read_tiles`` for the jnp read, 0 where the Pallas
    kernel serves it."""
    if _pallas_read(cache_spec):
        return 0
    return mixed_read_tiles(token_budget, n_slots)


def read_tiles_used(segment_lengths) -> int:
    """Tiles of the history read that a step's packing fills: ceil(n / L)
    for each slot's segment of n rows (a decode token is a segment of one),
    the rule ``_read_tiles`` applies inside the program."""
    return sum(-(-n // READ_TILE) for n in segment_lengths)


class KVCache(NamedTuple):
    k: jnp.ndarray  # (B, S_max, kv_dim)  flat: n_kv_heads * head_dim
    v: jnp.ndarray  # (B, S_max, kv_dim)


def init_attention(init: Initializer, name: str, cfg: ModelConfig):
    p = {
        "wq": init_linear(init, f"{name}/wq", cfg.d_model, cfg.q_dim, cfg.qkv_bias),
        "wk": init_linear(init, f"{name}/wk", cfg.d_model, cfg.kv_dim, cfg.qkv_bias),
        "wv": init_linear(init, f"{name}/wv", cfg.d_model, cfg.kv_dim, cfg.qkv_bias),
        "wo": init_linear(init, f"{name}/wo", cfg.q_dim, cfg.d_model),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"w": init.ones(f"{name}/qn", (cfg.head_dim,))}
        p["k_norm"] = {"w": init.ones(f"{name}/kn", (cfg.head_dim,))}
    return p


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, max_len, cfg.kv_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def _flat_kv_pspec(ctx: TPContext):
    # (B, S, kv_dim): batch over data, kv_dim over model; seq over data for
    # long-context batch=1 shapes (constrain() drops non-dividing entries)
    return (ctx.batch, ctx.seq_axis, ctx.axis if ctx.tp else None)


def _qkv(ctx: TPContext, params, x, cfg: ModelConfig, positions):
    B, S = x.shape[:2]
    q = column_linear(ctx, x, params["wq"]["w"], params["wq"].get("b"))
    k = column_linear(ctx, x, params["wk"]["w"], params["wk"].get("b"))
    v = column_linear(ctx, x, params["wv"]["w"], params["wv"].get("b"))
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"]["w"])
        k = rms_norm(k, params["k_norm"]["w"])
    if positions is not None:
        rope = make_rope(positions, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, rope)
        k = apply_rope(k, rope)
    return q, k.reshape(B, S, cfg.kv_dim), v  # k/v flat


def _attend_block(q, k, v, q_pos, t_pos, *, causal, window, scale, kv_heads):
    """q (B,Sq,H,hd); k/v flat (B,T,kv_dim); t_pos (T,) key positions,
    shared across the batch. -> (B,Sq,H*hd).

    q_pos is (Sq,) when positions are shared across the batch, or (B,Sq)
    for per-slot positions (continuous-batching decode)."""
    B, Sq, H, hd = q.shape
    T = k.shape[1]
    KV = kv_heads
    G = H // KV
    kh = k.reshape(B, T, KV, hd)
    vh = v.reshape(B, T, KV, hd)
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bsngd,btnd->bnsgt", qg, kh,
                        preferred_element_type=jnp.float32) * scale
    tp = t_pos[None, :]
    if causal:
        valid = tp <= q_pos[..., :, None]
    else:
        valid = jnp.broadcast_to(tp >= 0, q_pos.shape[:-1] + (Sq, T))
    if window is not None:
        valid = valid & (tp > q_pos[..., :, None] - window)
    if valid.ndim == 2:
        valid = valid[None]                        # (1 or B, Sq, T)
    scores = jnp.where(valid[:, None, :, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bnsgt,btnd->bsngd", probs, vh)
    return out.reshape(B, Sq, H * hd)


def _attend(q, k, v, q_pos, t_pos, *, causal, window, scale, kv_heads,
            chunk: int = _Q_CHUNK):
    """q-chunked attention: scores transient bounded to (B, chunk, H, T)."""
    B, S, H, hd = q.shape
    if S <= chunk:
        return _attend_block(q, k, v, q_pos, t_pos, causal=causal,
                             window=window, scale=scale, kv_heads=kv_heads)
    while S % chunk != 0:
        chunk //= 2
    nq = S // chunk
    qc = q.reshape(B, nq, chunk, H, hd).swapaxes(0, 1)     # (nq,B,c,H,hd)
    pc = q_pos.reshape(nq, chunk)

    def body(_, xs):
        q_i, pos_i = xs
        out = _attend_block(q_i, k, v, pos_i, t_pos, causal=causal,
                            window=window, scale=scale, kv_heads=kv_heads)
        return None, out

    _, outs = jax.lax.scan(body, None, (qc, pc))           # (nq,B,c,H*hd)
    return outs.swapaxes(0, 1).reshape(B, S, H * hd)


def attention(
    ctx: TPContext,
    params,
    x: jnp.ndarray,
    cfg: ModelConfig,
    *,
    pos: jnp.ndarray,                  # int32 scalar: first position of x
    cache: Optional[KVCache] = None,   # None => no-cache (training) path
    window: Optional[int] = None,
    causal: bool = True,
    cross_kv: Optional[KVCache] = None,  # encoder K/V for cross-attention
):
    """Unified attention: training (no cache), prefill (cache write),
    decode (S==1 cache append), and cross-attention (cross_kv given).

    Returns (out (B,S,d_model), new_cache).
    """
    B, S = x.shape[:2]
    scale = cfg.head_dim**-0.5
    a = ctx.axis if ctx.tp else None

    if cross_kv is not None:
        q = column_linear(ctx, x, params["wq"]["w"], params["wq"].get("b"))
        q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = rms_norm(q, params["q_norm"]["w"])
        T = cross_kv.k.shape[1]
        t_pos = jnp.arange(T, dtype=jnp.int32)
        out = _attend(q, cross_kv.k.astype(q.dtype), cross_kv.v.astype(q.dtype),
                      jnp.zeros((S,), jnp.int32), t_pos, causal=False,
                      window=None, scale=scale, kv_heads=cfg.n_kv_heads)
        out = constrain(ctx, out, ctx.batch, None, a)
        y = row_linear(ctx, out, params["wo"]["w"], n_tokens=B * S)
        return y, cache

    positions = pos + jnp.arange(S, dtype=jnp.int32)[None, :]  # (1,S) bcast
    q, k_new, v_new = _qkv(ctx, params, x, cfg, positions)

    if cache is None:
        t_pos = positions[0]
        q_pos = positions[0]
        k_all, v_all = k_new, v_new
    else:
        T = cache.k.shape[1]
        if ctx.seq_axis is not None and S == 1:
            # seq-sharded cache (long-context decode): a dynamic-update-slice
            # on the sharded dim gets SPMD-partitioned into scatter ops that
            # XLA-CPU aborts on; a masked select partitions trivially and
            # costs one cache-sized pass (which decode attention does anyway)
            sel = (jnp.arange(T, dtype=jnp.int32) == pos)[None, :, None]
            k_all = jnp.where(sel, k_new.astype(cache.k.dtype), cache.k)
            v_all = jnp.where(sel, v_new.astype(cache.v.dtype), cache.v)
        else:
            k_all = jax.lax.dynamic_update_slice(
                cache.k, k_new.astype(cache.k.dtype), (0, pos, 0))
            v_all = jax.lax.dynamic_update_slice(
                cache.v, v_new.astype(cache.v.dtype), (0, pos, 0))
        pspec = _flat_kv_pspec(ctx)
        k_all = constrain(ctx, k_all, *pspec)
        v_all = constrain(ctx, v_all, *pspec)
        cache = KVCache(k=k_all, v=v_all)
        t_pos = jnp.arange(T, dtype=jnp.int32)
        q_pos = pos + jnp.arange(S, dtype=jnp.int32)

    out = _attend(q, k_all.astype(q.dtype), v_all.astype(q.dtype), q_pos, t_pos,
                  causal=causal, window=window, scale=scale,
                  kv_heads=cfg.n_kv_heads)
    out = constrain(ctx, out, ctx.batch, None, a)
    y = row_linear(ctx, out, params["wo"]["w"], n_tokens=B * S)
    return y, cache


def _paged_attend_kernel(q, pool_k, pool_v, tables, hist_len, q_pos,
                         k_extra=None, v_extra=None, t_extra=None,
                         row_map=None, *,
                         cache_spec: KVCacheSpec, cfg: ModelConfig,
                         window: Optional[int]):
    """Route one paged read through the gather-free Pallas kernel
    (``kernels/paged_attention``): walk each row's block-table entries in
    VMEM with online softmax instead of gathering ``pool[tables]`` at full
    capacity through HBM, dequantizing MX wire blocks in-kernel. All three
    paged geometries (decode, chunk, mixed) land here; q is (R, Sq, H, hd)
    and the return is (R, Sq, H*hd) in q's dtype. ``row_map`` switches the
    block-table walk to virtual-region addressing over an exchanged pool
    (sequence-sharded read path)."""
    from repro.kernels.ops import interpret_mode
    from repro.kernels.paged_attention import paged_attention

    R, Sq = q.shape[:2]
    return paged_attention(
        q.reshape(R, Sq, -1), pool_k, pool_v, tables, hist_len, q_pos,
        k_extra, v_extra, t_extra, row_map,
        spec=cache_spec.mx, kv_heads=cfg.n_kv_heads,
        scale=cfg.head_dim**-0.5, window=window, out_dtype=q.dtype,
        interpret=interpret_mode())


def quantize_kv_pages(k: jnp.ndarray, v: jnp.ndarray, spec) -> tuple:
    """Quantize dense K/V (..., kv_dim) into wire pages (payload+scales pairs
    along the last axis) — the single append-path codec entry used by both
    prefill-insert and the decode write."""
    return mx.quantize(k, spec), mx.quantize(v, spec)


def _kv_entry(ctx: TPContext):
    """Block-dim spec entry for the paged pools: the kv axis once the pools
    are sequence-sharded, else replicated."""
    return ctx.kv_axis if ctx.kv_sharded else None


def constrain_wire_pool(ctx: TPContext, pool: MXCompressed) -> MXCompressed:
    """Pin a wire-format pool to the canonical sharding (packed features over
    the model axis, block dim over the kv axis when sequence-sharded — like
    the dense pools). Used by every pool producer so the decode jit always
    sees one input sharding and compiles exactly once."""
    a = ctx.axis if ctx.tp else None
    return MXCompressed(
        *(constrain(ctx, arr, _kv_entry(ctx), None, a) for arr in pool))


def _virtual_pools(ctx: TPContext, pool_k, pool_v, tables, quantized: bool):
    """Sequence-sharded read half (DESIGN.md §Sequence-sharded pools):
    exchange exactly the blocks named by ``tables`` — wire-format
    (payload, scale) bytes for quantized pools, never the full pool — into
    kv-replicated VIRTUAL pools laid out in table order,
    ``V[r*nb + j] == pool[tables[r, j]]`` bit-for-bit. Downstream reads then
    see the same values as the replicated path, so outputs stay
    token-identical."""
    from repro.core.tp import pool_exchange

    if quantized:
        kp, ks, vp, vs = pool_exchange(
            ctx, [pool_k.payload, pool_k.scales, pool_v.payload,
                  pool_v.scales], tables)
        return MXCompressed(kp, ks), MXCompressed(vp, vs)
    vk, vv = pool_exchange(ctx, [pool_k, pool_v], tables)
    return vk, vv


def _sharded_append(ctx: TPContext, pool_k, pool_v, k_vals, v_vals,
                    blk, offs, quantized: bool):
    """Sequence-sharded write half: communication-free drop-mode scatters —
    each kv shard writes only the rows it owns (a GSPMD scatter on the
    sharded block dim would be partitioned into ops XLA-CPU aborts on; see
    the seq_axis note in ``attention``). ``k_vals``/``v_vals`` are the
    per-position rows ((N, wire/dense width), already quantized/cast)."""
    from repro.core.tp import pool_scatter

    if quantized:
        kp, ks, vp, vs = pool_scatter(
            ctx, [(pool_k.payload, k_vals.payload),
                  (pool_k.scales, k_vals.scales),
                  (pool_v.payload, v_vals.payload),
                  (pool_v.scales, v_vals.scales)], blk, offs)
        return MXCompressed(kp, ks), MXCompressed(vp, vs)
    pk, pv = pool_scatter(ctx, [(pool_k, k_vals), (pool_v, v_vals)],
                          blk, offs)
    return pk, pv


def paged_attention_decode(
    ctx: TPContext,
    params,
    x: jnp.ndarray,                    # (B, 1, d_model) — one token per slot
    cfg: ModelConfig,
    *,
    lengths: jnp.ndarray,              # (B,) int32 per-slot write position
    pool_k,                            # (n_blocks, block_size, kv_dim) dense,
    pool_v,                            #   or MXCompressed wire pools
    tables: jnp.ndarray,               # (B, max_blocks) int32 block ids
    window: Optional[int] = None,
    cache_spec: Optional[KVCacheSpec] = None,
):
    """One decode step against a paged KV cache (DESIGN.md §Paged cache).

    Writes the new K/V at block ``tables[b, lengths[b] // bs]`` offset
    ``lengths[b] % bs`` (a vectorized scatter), gathers each slot's logical
    sequence via its block-table row, and attends with per-slot masks.
    Inactive slots point at the null block; their writes and reads are
    garbage but masked out by the engine. Returns (out, pool_k, pool_v).

    With a quantized ``cache_spec`` the pools are ``MXCompressed`` wire
    arrays: the new K/V is quantized before the scatter and the pages are
    dequantized on read. With ``cache_spec.use_pallas`` the read side (dense
    or quantized) runs the gather-free Pallas kernel instead of the jnp
    ``pool[tables]`` gather.
    """
    B = x.shape[0]
    a = ctx.axis if ctx.tp else None
    positions = lengths[:, None]                                # (B, 1) RoPE
    q, k_new, v_new = _qkv(ctx, params, x, cfg, positions)
    quantized = cache_spec is not None and cache_spec.quantized

    bs = (pool_k.payload if quantized else pool_k).shape[1]
    block_ids = jnp.take_along_axis(tables, (lengths // bs)[:, None], axis=1)[:, 0]
    offs = lengths % bs

    if quantized:
        mxs = cache_spec.mx
        kq, vq = quantize_kv_pages(k_new[:, 0], v_new[:, 0], mxs)
        if ctx.kv_sharded:
            pool_k, pool_v = _sharded_append(
                ctx, pool_k, pool_v, kq, vq, block_ids, offs, True)
        else:
            pool_k = MXCompressed(
                payload=pool_k.payload.at[block_ids, offs].set(kq.payload),
                scales=pool_k.scales.at[block_ids, offs].set(kq.scales))
            pool_v = MXCompressed(
                payload=pool_v.payload.at[block_ids, offs].set(vq.payload),
                scales=pool_v.scales.at[block_ids, offs].set(vq.scales))
        # every producer of wire pools (this decode write and the engine's
        # prefill-insert) must constrain them to the SAME spec, or the
        # decode jit sees a new input sharding on its second step and
        # recompiles, breaking the engine's compile-once invariant
        pool_k = constrain_wire_pool(ctx, pool_k)
        pool_v = constrain_wire_pool(ctx, pool_v)
    else:
        if ctx.kv_sharded:
            pool_k, pool_v = _sharded_append(
                ctx, pool_k, pool_v, k_new[:, 0].astype(pool_k.dtype),
                v_new[:, 0].astype(pool_v.dtype), block_ids, offs, False)
        else:
            pool_k = pool_k.at[block_ids, offs].set(
                k_new[:, 0].astype(pool_k.dtype))
            pool_v = pool_v.at[block_ids, offs].set(
                v_new[:, 0].astype(pool_v.dtype))
        pool_k = constrain(ctx, pool_k, _kv_entry(ctx), None, a)
        pool_v = constrain(ctx, pool_v, _kv_entry(ctx), None, a)

    if ctx.kv_sharded:
        # exchange the table-named blocks (post-write: decode history runs
        # through the just-scattered token) into virtual pools; row b's
        # region is b, so the virtual table walk is row_map[b] * nb + j
        vpool_k, vpool_v = _virtual_pools(ctx, pool_k, pool_v, tables,
                                          quantized)

    if cache_spec is not None and cache_spec.use_pallas:
        # gather-free read: the kernel walks each slot's block-table row; the
        # token just scattered above is already in the pool, so row b's
        # history runs to lengths[b] + 1 and no in-step extras are needed
        if ctx.kv_sharded:
            out = _paged_attend_kernel(
                q, vpool_k, vpool_v, tables, lengths + 1, lengths[:, None],
                row_map=jnp.arange(B, dtype=jnp.int32),
                cache_spec=cache_spec, cfg=cfg, window=window)
        else:
            out = _paged_attend_kernel(
                q, pool_k, pool_v, tables, lengths + 1, lengths[:, None],
                cache_spec=cache_spec, cfg=cfg, window=window)
        out = constrain(ctx, out, ctx.batch, None, a)
        y = row_linear(ctx, out, params["wo"]["w"], n_tokens=B)
        return y, pool_k, pool_v

    if quantized:
        # gathered wire pages, logical (B, T, wire) like the dense layout;
        # the sharded virtual pool is already in table order (reshape, no
        # gather — same values bit-for-bit as pool[tables])
        if ctx.kv_sharded:
            k_pl = vpool_k.payload.reshape(B, -1, vpool_k.payload.shape[-1])
            k_sc = vpool_k.scales.reshape(B, -1, vpool_k.scales.shape[-1])
            v_pl = vpool_v.payload.reshape(B, -1, vpool_v.payload.shape[-1])
            v_sc = vpool_v.scales.reshape(B, -1, vpool_v.scales.shape[-1])
        else:
            k_pl = pool_k.payload[tables].reshape(B, -1, pool_k.payload.shape[-1])
            k_sc = pool_k.scales[tables].reshape(B, -1, pool_k.scales.shape[-1])
            v_pl = pool_v.payload[tables].reshape(B, -1, pool_v.payload.shape[-1])
            v_sc = pool_v.scales[tables].reshape(B, -1, pool_v.scales.shape[-1])
        k_all = mx.dequantize(MXCompressed(k_pl, k_sc), mxs, out_dtype=q.dtype)
        v_all = mx.dequantize(MXCompressed(v_pl, v_sc), mxs, out_dtype=q.dtype)
    elif ctx.kv_sharded:
        k_all = vpool_k.reshape(B, -1, cfg.kv_dim)
        v_all = vpool_v.reshape(B, -1, cfg.kv_dim)
    else:
        # (B, max_blocks, bs, kv) -> logical (B, T, kv); block j of a slot's
        # table holds that slot's positions [j*bs, (j+1)*bs)
        k_all = pool_k[tables].reshape(B, -1, cfg.kv_dim)
        v_all = pool_v[tables].reshape(B, -1, cfg.kv_dim)
    k_all = constrain(ctx, k_all, ctx.batch, None, a)
    v_all = constrain(ctx, v_all, ctx.batch, None, a)

    # per-slot causal mask: slot b attends to t <= lengths[b] (its current
    # token's position, just written above)
    out = _attend_block(q, k_all.astype(q.dtype), v_all.astype(q.dtype),
                        lengths[:, None],
                        jnp.arange(k_all.shape[1], dtype=jnp.int32),
                        causal=True, window=window, scale=cfg.head_dim**-0.5,
                        kv_heads=cfg.n_kv_heads)
    out = constrain(ctx, out, ctx.batch, None, a)
    y = row_linear(ctx, out, params["wo"]["w"], n_tokens=B)
    return y, pool_k, pool_v


# sentinel logical position for pool entries that must never be attended to
# (unwritten / stale history at t >= start): larger than any real position,
# so the causal mask t <= q_pos kills them unconditionally
_T_INVALID = jnp.int32(2**30)


def paged_attention_chunk(
    ctx: TPContext,
    params,
    x: jnp.ndarray,                    # (1, C, d_model) — one prompt chunk
    cfg: ModelConfig,
    *,
    start: jnp.ndarray,                # int32 scalar: position of x[:, 0]
    table_row: jnp.ndarray,            # (max_blocks,) int32: the slot's blocks
    pool_k,                            # (n_blocks, block_size, kv_dim) dense,
    pool_v,                            #   or MXCompressed wire pools
    window: Optional[int] = None,
    cache_spec: Optional[KVCacheSpec] = None,
):
    """Chunked-prefill attention for ONE slot against the paged cache.

    The slot's already-written history (positions < ``start``) is gathered
    through its block-table row and attended together with the current
    chunk's K/V — the chunk stays in compute precision while history reads
    at pool precision (dense cast or MX dequantize), mirroring what decode
    sees later. The chunk's K/V is then appended into the pools at positions
    ``start + [0, C)``; positions whose covering block is unallocated (pads
    past the slot's need) fall through to the null block. Unlike whole-prompt
    prefill this never materializes a dense full-prompt cache, and its shapes
    are independent of prompt length — the engine compiles it exactly once.

    Returns (out (1, C, d_model), pool_k, pool_v).
    """
    B, C = x.shape[:2]
    a = ctx.axis if ctx.tp else None
    scale = cfg.head_dim**-0.5
    p = start + jnp.arange(C, dtype=jnp.int32)                  # chunk positions
    q, k_new, v_new = _qkv(ctx, params, x, cfg, p[None, :])
    quantized = cache_spec is not None and cache_spec.quantized

    nb = table_row.shape[0]
    bs = (pool_k.payload if quantized else pool_k).shape[1]
    cap = nb * bs
    # scatter coordinates: block covering each chunk position (0 = null block
    # for positions past the table, so over-capacity pads write harmlessly)
    blk = jnp.where(p < cap, table_row[jnp.clip(p // bs, 0, nb - 1)], 0)
    offs = p % bs

    # read history BEFORE the append so the chunk's own K/V is counted once
    # (in compute precision as extras, not through the pool roundtrip)
    if ctx.kv_sharded:
        # one table row => one virtual region holding the slot's blocks in
        # table order (exchanged pre-append, matching the read-then-write
        # order of the replicated path)
        vpool_k, vpool_v = _virtual_pools(ctx, pool_k, pool_v,
                                          table_row[None], quantized)
    if cache_spec is not None and cache_spec.use_pallas:
        # gather-free read: one table row (R=1), history below ``start``,
        # the chunk itself folded in as compute-precision extras
        out = _paged_attend_kernel(
            q, vpool_k if ctx.kv_sharded else pool_k,
            vpool_v if ctx.kv_sharded else pool_v, table_row[None],
            jnp.asarray(start, jnp.int32).reshape(1), p[None, :],
            k_new[0].astype(q.dtype), v_new[0].astype(q.dtype), p[None, :],
            jnp.zeros((1,), jnp.int32) if ctx.kv_sharded else None,
            cache_spec=cache_spec, cfg=cfg, window=window)
    else:
        t_hist = jnp.arange(cap, dtype=jnp.int32)
        t_hist = jnp.where(t_hist < start, t_hist, _T_INVALID)
        if quantized:
            mxs = cache_spec.mx
            if ctx.kv_sharded:
                k_wire = MXCompressed(vpool_k.payload.reshape(1, cap, -1),
                                      vpool_k.scales.reshape(1, cap, -1))
                v_wire = MXCompressed(vpool_v.payload.reshape(1, cap, -1),
                                      vpool_v.scales.reshape(1, cap, -1))
            else:
                k_wire = MXCompressed(
                    pool_k.payload[table_row].reshape(1, cap, -1),
                    pool_k.scales[table_row].reshape(1, cap, -1))
                v_wire = MXCompressed(
                    pool_v.payload[table_row].reshape(1, cap, -1),
                    pool_v.scales[table_row].reshape(1, cap, -1))
            k_hist = mx.dequantize(k_wire, mxs, out_dtype=q.dtype)
            v_hist = mx.dequantize(v_wire, mxs, out_dtype=q.dtype)
        elif ctx.kv_sharded:
            k_hist = vpool_k.reshape(1, cap, -1).astype(q.dtype)
            v_hist = vpool_v.reshape(1, cap, -1).astype(q.dtype)
        else:
            k_hist = pool_k[table_row].reshape(1, cap, -1).astype(q.dtype)
            v_hist = pool_v[table_row].reshape(1, cap, -1).astype(q.dtype)

        k_all = jnp.concatenate([k_hist, k_new.astype(q.dtype)], axis=1)
        v_all = jnp.concatenate([v_hist, v_new.astype(q.dtype)], axis=1)
        t_pos = jnp.concatenate([t_hist, p])
        out = _attend(q, k_all, v_all, p, t_pos, causal=True, window=window,
                      scale=scale, kv_heads=cfg.n_kv_heads)

    # append the chunk into the pools (wire-quantized via the shared codec
    # entry when the cache spec says so) — same constrain discipline as the
    # decode write so the compiled programs agree on pool sharding
    if quantized:
        kq, vq = quantize_kv_pages(k_new[0], v_new[0], cache_spec.mx)
        if ctx.kv_sharded:
            pool_k, pool_v = _sharded_append(
                ctx, pool_k, pool_v, kq, vq, blk, offs, True)
            pool_k = constrain_wire_pool(ctx, pool_k)
            pool_v = constrain_wire_pool(ctx, pool_v)
        else:
            pool_k = constrain_wire_pool(ctx, MXCompressed(
                payload=pool_k.payload.at[blk, offs].set(kq.payload),
                scales=pool_k.scales.at[blk, offs].set(kq.scales)))
            pool_v = constrain_wire_pool(ctx, MXCompressed(
                payload=pool_v.payload.at[blk, offs].set(vq.payload),
                scales=pool_v.scales.at[blk, offs].set(vq.scales)))
    else:
        if ctx.kv_sharded:
            pool_k, pool_v = _sharded_append(
                ctx, pool_k, pool_v, k_new[0].astype(pool_k.dtype),
                v_new[0].astype(pool_v.dtype), blk, offs, False)
        else:
            pool_k = pool_k.at[blk, offs].set(k_new[0].astype(pool_k.dtype))
            pool_v = pool_v.at[blk, offs].set(v_new[0].astype(pool_v.dtype))
        pool_k = constrain(ctx, pool_k, _kv_entry(ctx), None, a)
        pool_v = constrain(ctx, pool_v, _kv_entry(ctx), None, a)

    out = constrain(ctx, out, ctx.batch, None, a)
    y = row_linear(ctx, out, params["wo"]["w"], n_tokens=B * C)
    return y, pool_k, pool_v


def paged_attention_mixed(
    ctx: TPContext,
    params,
    x: jnp.ndarray,                    # (1, T, d_model) — the flattened budget
    cfg: ModelConfig,
    *,
    positions: jnp.ndarray,            # (T,) int32 per-token positions
    slot_ids: jnp.ndarray,             # (T,) int32 owning slot per token
    slot_starts: jnp.ndarray,          # (n_slots,) int32 pre-step history end
    valid: jnp.ndarray,                # (T,) bool — False rows are budget pads
    is_decode: jnp.ndarray,            # (T,) bool — decode vs prefill token
    tables: jnp.ndarray,               # (n_slots, max_blocks) int32 block ids
    pool_k,                            # (n_blocks, block_size, kv_dim) dense,
    pool_v,                            #   or MXCompressed wire pools
    window: Optional[int] = None,
    cache_spec: Optional[KVCacheSpec] = None,
):
    """ONE mixed-batch token-budget step: several slots' prefill chunks plus
    one decode token per DECODING slot, flattened into a single (1, T) batch
    and attended against the paged cache in one program.

    Token t attends ITS slot's paged history through
    ``tables[slot_ids[t]]`` (valid below ``slot_starts[slot_ids[t]]`` —
    everything written before this step) and the current batch's same-slot
    tokens at positions <= its own. The jnp read gathers each slot's history
    once per query TILE, not once per token: a slot's rows form one
    contiguous segment, cut into tiles of ``READ_TILE`` rows, and the
    ``mixed_read_tiles(T, n_slots)`` tiles each gather their slot's history
    (``pool[tables[tile_slot]]``), score their rows against it and apply the
    probabilities to it; scores travel between tiles and rows by each row's
    (tile, lane). Under ``cache_spec.use_pallas`` each row instead walks its
    slot's table in the gather-free kernel. Precision mirrors the split
    chunk/decode pair exactly: prefill tokens see same-chunk neighbours in
    COMPUTE precision (what ``paged_attention_chunk`` did), while a decode
    token sees its own
    just-written K/V at POOL precision (dense-dtype cast or MX round-trip —
    what ``paged_attention_decode`` reads back after its scatter). All new
    K/V is then appended into the pools through the shared
    ``quantize_kv_pages`` codec entry; pad rows (``valid`` False) read zero
    and write into the reserved null block. Shapes depend only on
    (token_budget, n_slots, max_blocks), so the engine compiles this exactly
    once.

    Everything between the QKV and output projections (the history read,
    the attention, the pool append) runs under the ``paged_attention``
    named scope, whichever read path serves it, so a device trace credits
    that work to one name.

    Returns (out (1, T, d_model), pool_k, pool_v).
    """
    B, T = x.shape[:2]
    q, k_new, v_new = _qkv(ctx, params, x, cfg, positions[None, :])
    with jax.named_scope(PAGED_ATTENTION_SCOPE):
        out, pool_k, pool_v = _mixed_read_attend_append(
            ctx, cfg, q, k_new, v_new, positions=positions,
            slot_ids=slot_ids, slot_starts=slot_starts, valid=valid,
            is_decode=is_decode, tables=tables, pool_k=pool_k,
            pool_v=pool_v, window=window, cache_spec=cache_spec)
    y = row_linear(ctx, out, params["wo"]["w"], n_tokens=B * T)
    return y, pool_k, pool_v


def _read_tiles(slot_ids, valid, n_slots: int):
    """Map the mixed step's flattened rows onto read tiles, inside the
    program. ``build_mixed_batch`` lays each slot's rows out as one
    contiguous segment; slot s's n_s rows fill ceil(n_s / READ_TILE) tiles,
    numbered slot by slot, and its i-th row sits in the segment's tile
    i // READ_TILE at lane i % READ_TILE. Pad rows get tile N, which
    scatters drop. Returns (tile (T,), lane (T,), tile_slot (N,)) with N =
    ``mixed_read_tiles(T, n_slots)``; tiles no row fills read slot 0."""
    T = slot_ids.shape[0]
    n = mixed_read_tiles(T, n_slots)
    rows = jnp.arange(T, dtype=jnp.int32)
    seg_len = jax.ops.segment_sum(valid.astype(jnp.int32), slot_ids,
                                  num_segments=n_slots)
    seg_off = jax.ops.segment_min(jnp.where(valid, rows, T), slot_ids,
                                  num_segments=n_slots)
    seg_tiles = -(-seg_len // READ_TILE)
    first_tile = jnp.cumsum(seg_tiles) - seg_tiles
    i = jnp.where(valid, rows - seg_off[slot_ids], 0)
    tile = jnp.where(valid, first_tile[slot_ids] + i // READ_TILE, n)
    tile_slot = jnp.zeros((n,), jnp.int32).at[tile].set(slot_ids, mode="drop")
    return tile, i % READ_TILE, tile_slot


def _attend_tiled(q, k_hist, v_hist, k_step, v_step, tile, lane, q_pos,
                  t_hist, t_step, *, window, scale, kv_heads, kv_groups):
    """Attention of the mixed step's T rows over each row's slot history,
    held once per tile, plus the step's own K/V. q (T, H, hd); k/v_hist
    (N, cap, kv_dim), tile n holding slot ``tile_slot[n]``'s history; k/v_step
    (T, kv_dim); row t sits in ``tile[t]`` at ``lane[t]`` (pads: tile N);
    t_hist (T, cap) and t_step (T, T) are each row's key positions, invalid
    keys at ``_T_INVALID``. History scores are made per tile and brought
    back to rows; the step's scores are made per row; one softmax in f32
    runs over all cap + T of a row's scores, and its probabilities, cast to
    the value dtype, weigh the history per tile and the step's values per
    row, both summed in f32. Returns (T, H*hd) in the value dtype.

    The kv heads split into ``kv_groups`` groups of contiguous heads, one
    per tensor-parallel shard of kv_dim. The history einsums take the groups
    as a batch dimension, which the partitioner splits, and the heads of a
    group one at a time, each on its lane slice of the flat history: with
    every head a batch dimension, XLA relays the whole gathered history out
    before the einsum."""
    T, H, hd = q.shape
    n_tiles, cap = k_hist.shape[:2]
    G = H // kv_heads
    local = kv_heads // kv_groups
    f32 = jnp.float32
    qg = q.reshape(T, kv_groups, local, G, hd)
    kh = k_hist.reshape(n_tiles, cap, kv_groups, local * hd)
    vh = v_hist.reshape(n_tiles, cap, kv_groups, local * hd)
    # pad rows read whichever tile is last; the caller zeroes their outputs
    at = (jnp.minimum(tile, n_tiles - 1), lane)

    def tiled(rows):                  # (T, ...) -> (N, READ_TILE, ...)
        x = jnp.zeros((n_tiles, READ_TILE) + rows.shape[1:], rows.dtype)
        return x.at[tile, lane].set(rows, mode="drop")

    def head(x, j):                   # head j of each group: its lanes
        return x[..., j * hd:(j + 1) * hd]

    q_tiles = tiled(qg)
    s_hist = jnp.stack(
        [jnp.einsum("nlmgd,ncmd->nlmgc", q_tiles[:, :, :, j], head(kh, j),
                    preferred_element_type=f32)[at]
         for j in range(local)], axis=2).reshape(T, kv_heads, G, cap)
    s_step = jnp.einsum("tkgd,skd->tkgs", qg.reshape(T, kv_heads, G, hd),
                        k_step.reshape(T, kv_heads, hd),
                        preferred_element_type=f32)

    def keep(t_pos):                  # (T, X) key positions -> (T, 1, 1, X)
        ok = t_pos <= q_pos[:, None]
        if window is not None:
            ok = ok & (t_pos > q_pos[:, None] - window)
        return ok[:, None, None, :]

    scores = jnp.concatenate(
        [jnp.where(keep(t_hist), s_hist * scale, NEG_INF),
         jnp.where(keep(t_step), s_step * scale, NEG_INF)], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_hist.dtype)
    p_hist = probs[..., :cap].reshape(T, kv_groups, local, G, cap)
    out = jnp.stack(
        [jnp.einsum("nlmgc,ncmd->nlmgd", tiled(p_hist[:, :, j]),
                    head(vh, j), preferred_element_type=f32)[at]
         for j in range(local)], axis=2).reshape(T, kv_heads, G, hd)
    out = out + jnp.einsum("tkgs,skd->tkgd", probs[..., cap:],
                           v_step.reshape(T, kv_heads, hd),
                           preferred_element_type=f32)
    return out.reshape(T, H * hd).astype(probs.dtype)


def _mixed_read_attend_append(ctx: TPContext, cfg: ModelConfig, q, k_new,
                              v_new, *, positions, slot_ids, slot_starts,
                              valid, is_decode, tables, pool_k, pool_v,
                              window, cache_spec):
    """The body of ``paged_attention_mixed`` past its QKV projection:
    returns (attention out (1, T, H*hd), pool_k, pool_v)."""
    T = q.shape[1]
    a = ctx.axis if ctx.tp else None
    scale = cfg.head_dim**-0.5
    quantized = cache_spec is not None and cache_spec.quantized
    qt = q[0][:, None]                                  # (T, 1, H, hd)

    my_tables = tables[slot_ids]                        # (T, max_blocks)
    nb = tables.shape[1]
    bs = (pool_k.payload if quantized else pool_k).shape[1]
    cap = nb * bs

    # per-row history end: the slot's pre-step write position (everything
    # this step appends is attended in-batch instead)
    start = slot_starts[slot_ids]                       # (T,)
    if quantized:
        mxs = cache_spec.mx
        kq, vq = quantize_kv_pages(k_new[0], v_new[0], mxs)
        k_rt = mx.dequantize(kq, mxs, out_dtype=q.dtype)
        v_rt = mx.dequantize(vq, mxs, out_dtype=q.dtype)
    else:
        k_rt = k_new[0].astype(pool_k.dtype).astype(q.dtype)
        v_rt = v_new[0].astype(pool_v.dtype).astype(q.dtype)

    # in-batch K/V: decode tokens read their own write back at pool
    # precision (split-decode semantics); prefill tokens stay in compute
    # precision (split-chunk semantics)
    k_step = jnp.where(is_decode[:, None], k_rt, k_new[0].astype(q.dtype))
    v_step = jnp.where(is_decode[:, None], v_rt, v_new[0].astype(q.dtype))
    same = (slot_ids[None, :] == slot_ids[:, None]) & valid[None, :]
    t_step = jnp.where(same, positions[None, :], _T_INVALID)    # (T, T)

    if ctx.kv_sharded:
        # exchange ONE region per SLOT (not per token: T tokens share
        # n_slots tables, so the wire moves n_slots * cap positions, the
        # slots' resident context); token t's region is slot_ids[t]
        vpool_k, vpool_v = _virtual_pools(ctx, pool_k, pool_v, tables,
                                          quantized)

    if _pallas_read(cache_spec):
        # gather-free read: each flattened token walks its OWN slot's table
        # row in the kernel — the pool[tables[tile_slot]] HBM gather of the
        # jnp path below never materializes. The step's in-batch K/V
        # rides along as extras with the (T, T) same-slot position mask.
        out = _paged_attend_kernel(
            qt, vpool_k if ctx.kv_sharded else pool_k,
            vpool_v if ctx.kv_sharded else pool_v, my_tables, start,
            positions[:, None], k_step, v_step, t_step,
            slot_ids if ctx.kv_sharded else None,
            cache_spec=cache_spec, cfg=cfg, window=window)
    else:
        t_hist = jnp.arange(cap, dtype=jnp.int32)[None, :]      # (1, cap)
        t_hist = jnp.where(t_hist < start[:, None], t_hist, _T_INVALID)
        ns = tables.shape[0]
        tile, lane, tile_slot = _read_tiles(slot_ids, valid, ns)
        tile_tables = tables[tile_slot]

        def per_tile(arr):            # one history per tile: (N, cap, width)
            if ctx.kv_sharded:        # the exchange's per-slot regions
                return arr.reshape(ns, cap, -1)[tile_slot]
            return arr[tile_tables].reshape(tile_slot.shape[0], cap, -1)

        pk, pv = (vpool_k, vpool_v) if ctx.kv_sharded else (pool_k, pool_v)
        if quantized:
            k_hist = mx.dequantize(MXCompressed(*map(per_tile, pk)), mxs,
                                   out_dtype=q.dtype)
            v_hist = mx.dequantize(MXCompressed(*map(per_tile, pv)), mxs,
                                   out_dtype=q.dtype)
        else:
            k_hist = per_tile(pk).astype(q.dtype)
            v_hist = per_tile(pv).astype(q.dtype)
        kv = cfg.n_kv_heads
        out = _attend_tiled(
            q[0], k_hist, v_hist, k_step, v_step, tile, lane, positions,
            t_hist, t_step, window=window, scale=scale, kv_heads=kv,
            kv_groups=ctx.tp_size if kv % ctx.tp_size == 0 else kv)[:, None]
    out = out[:, 0][None]                               # (1, T, H*hd)
    # pad rows attend nothing real: both reads give them zeros, so what the
    # next layer appends into the null block is finite and the same
    out = jnp.where(valid[None, :, None], out, 0)

    # append every real token's K/V into the pools; pads fall into the null
    # block. Same codec entry + constrain discipline as the split writers.
    blk = jnp.where(valid & (positions < cap),
                    my_tables[jnp.arange(T), jnp.clip(positions // bs, 0, nb - 1)],
                    0)
    offs = positions % bs
    if quantized:
        if ctx.kv_sharded:
            pool_k, pool_v = _sharded_append(
                ctx, pool_k, pool_v, kq, vq, blk, offs, True)
            pool_k = constrain_wire_pool(ctx, pool_k)
            pool_v = constrain_wire_pool(ctx, pool_v)
        else:
            pool_k = constrain_wire_pool(ctx, MXCompressed(
                payload=pool_k.payload.at[blk, offs].set(kq.payload),
                scales=pool_k.scales.at[blk, offs].set(kq.scales)))
            pool_v = constrain_wire_pool(ctx, MXCompressed(
                payload=pool_v.payload.at[blk, offs].set(vq.payload),
                scales=pool_v.scales.at[blk, offs].set(vq.scales)))
    else:
        if ctx.kv_sharded:
            pool_k, pool_v = _sharded_append(
                ctx, pool_k, pool_v, k_new[0].astype(pool_k.dtype),
                v_new[0].astype(pool_v.dtype), blk, offs, False)
        else:
            pool_k = pool_k.at[blk, offs].set(k_new[0].astype(pool_k.dtype))
            pool_v = pool_v.at[blk, offs].set(v_new[0].astype(pool_v.dtype))
        pool_k = constrain(ctx, pool_k, _kv_entry(ctx), None, a)
        pool_v = constrain(ctx, pool_v, _kv_entry(ctx), None, a)
    return constrain(ctx, out, ctx.batch, None, a), pool_k, pool_v


def attention_specs(cfg: ModelConfig, ctx: TPContext):
    """PartitionSpec pytree matching init_attention output."""
    from jax.sharding import PartitionSpec as P

    a = ctx.axis if ctx.tp else None
    d = ctx.wdata
    lin = lambda fin_s, fout_s: {"w": P(fin_s, fout_s)}

    def with_bias(base, fout_s):
        if cfg.qkv_bias:
            return {**base, "b": P(fout_s)}
        return base

    p = {
        "wq": with_bias(lin(d, a), a),
        "wk": with_bias(lin(d, a), a),
        "wv": with_bias(lin(d, a), a),
        "wo": lin(a, d),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"w": P(None)}
        p["k_norm"] = {"w": P(None)}
    return p
