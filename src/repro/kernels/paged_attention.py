"""Pallas kernel: gather-free paged attention over block-table pools.

ONE kernel serves all three paged read geometries of ``models/attention.py``
— split decode, split prefill chunk, and the unified mixed token-budget step.
The grid is (rows, blocks-per-table): for each query row the kernel walks
that row's block-table entries via scalar prefetch, DMA-ing exactly one pool
block at a time into VMEM — the ``pool[table].reshape(cap, ...)``
full-capacity HBM gather of the jnp reference path never happens. MX
wire-format pools are dequantized per streamed block inside the body with
the codec primitives from ``mx_dequant``; dense pools run the same body
through a cast, so both formats share one kernel behind a static switch.

Running softmax statistics (m, l, acc) persist in VMEM scratch across the
innermost (block) grid dimension — the flash-attention recurrence — and the
current step's compute-precision K/V (``k_extra``/``v_extra``: the prefill
chunk's own tokens, or the mixed step's in-batch K/V) is folded in at the
last block before normalization.

Layout keeps Mosaic's tiling rules: queries enter pre-grouped per KV head
as (rows, KV, Sq*G, hd) and leave the same way (the wrapper transposes), so
the body never reshapes lanes; each KV head is a 128-aligned lane slice of
the flat (block, kv_dim) pool block, attended with 2-D matmuls; query and
extra positions arrive as whole-array-tail blocks ((Sq*G, 1) and (1, E)).

Masking follows the same finite ``-1e30`` convention as
``models/attention.py``: initializing the running max at ``NEG_INF`` (not
``-inf``) makes a fully-masked row degrade to a uniform average over its
keys — exactly what ``jax.nn.softmax`` over all-``NEG_INF`` scores produces
in the jnp oracle — so pad rows match instead of going NaN.

On CPU the kernel runs in interpret mode (the parity oracle + CI path); on
TPU the same code lowers through Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import MXSpec
from repro.kernels.mx_dequant import _dequant_tile

__all__ = ["paged_attention"]

NEG_INF = -1e30


def _kernel(tables_ref, hist_ref, *refs, spec, kv_heads, head_dim,
            block_size, n_blocks, scale, window, has_extra,
            has_row_map=False):
    if has_row_map:
        # third scalar-prefetch operand (the virtual-region row map) — only
        # the index maps consume it; the body skips past its ref
        refs = refs[1:]
    q_ref = refs[0]
    if spec is None:
        k_ref, v_ref = refs[1:3]
        i = 3
    else:
        kp_ref, ks_ref, vp_ref, vs_ref = refs[1:5]
        i = 5
    qp_ref = refs[i]
    i += 1
    if has_extra:
        ke_ref, ve_ref, te_ref = refs[i:i + 3]
        i += 3
    out_ref, m_scr, l_scr, acc_scr = refs[i:i + 4]

    r, j = pl.program_id(0), pl.program_id(1)
    hd = head_dim

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q_pos = qp_ref[0]                                  # (Sq*G, 1) int32
    hist = hist_ref[r]

    def accumulate(k, v, valid):
        """Fold one batch of keys into the online-softmax state, one KV head
        (a 128-aligned lane slice of the flat K/V) at a time. k/v are
        (T', kv_dim) fp32; valid is the (Sq*G, T') mask."""
        for h in range(kv_heads):                      # static unroll
            kh = k[:, h * hd:(h + 1) * hd]
            vh = v[:, h * hd:(h + 1) * hd]
            s = jax.lax.dot_general(
                q_ref[0, h].astype(jnp.float32), kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (Sq*G, T')
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_scr[h]                          # (Sq*G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[h] = l_scr[h] * alpha + p.sum(axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jnp.dot(
                p, vh, preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    # this grid step's pool block: positions [j*bs, (j+1)*bs) of the row's
    # logical sequence, valid below the row's history end and causally
    t_blk = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    tv = (t_blk < hist) & (t_blk <= q_pos)
    if window is not None:
        tv = tv & (t_blk > q_pos - window)
    if spec is None:
        k = k_ref[0].astype(jnp.float32)                       # (bs, kv_dim)
        v = v_ref[0].astype(jnp.float32)
    else:
        k = _dequant_tile(kp_ref[0], ks_ref[0], spec)
        v = _dequant_tile(vp_ref[0], vs_ref[0], spec)
    accumulate(k, v, tv)

    @pl.when(j == n_blocks - 1)
    def _finalize():
        if has_extra:
            te = te_ref[0]                                     # (1, E) int32
            ev = te <= q_pos                                   # (Sq*G, E)
            if window is not None:
                ev = ev & (te > q_pos - window)
            accumulate(ke_ref[...].astype(jnp.float32),
                       ve_ref[...].astype(jnp.float32), ev)
        for h in range(kv_heads):
            out_ref[0, h] = (acc_scr[h] / l_scr[h]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "spec", "kv_heads", "scale", "window", "out_dtype", "interpret"))
def paged_attention(
    q: jnp.ndarray,            # (R, Sq, H*hd) query rows
    pool_k,                    # (n_blocks, bs, kv_dim) dense, or MXCompressed
    pool_v,                    #   wire pools (payload+scales)
    tables: jnp.ndarray,       # (R, nb) int32 per-row block-table row
    hist_len: jnp.ndarray,     # (R,) int32 history end (exclusive) per row
    q_pos: jnp.ndarray,        # (R, Sq) int32 query positions
    k_extra=None,              # (E, kv_dim) compute-precision in-step keys
    v_extra=None,              # (E, kv_dim)
    t_extra=None,              # (R, E) int32 positions (or broadcastable (1, E))
    row_map=None,              # (R,) int32 virtual region per row (sharded
                               #   pools: block j of row r lives at pool row
                               #   row_map[r] * nb + j, see below)
    *,
    spec: MXSpec | None = None,  # None = dense pools
    kv_heads: int,
    scale: float,
    window=None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    """Gather-free paged GQA attention: walk each row's block table, stream
    pool blocks through VMEM with online softmax, fold in optional in-step
    K/V extras, return (R, Sq, H*hd).

    Row r attends pool positions ``t < hist_len[r]`` (causally vs
    ``q_pos[r]``, optionally sliding-window limited) read at pool precision
    (dense cast or fused MX dequant), plus the shared ``k_extra`` keys at
    positions ``t_extra[r]`` in compute precision. Geometry per caller:
    decode (R=B, Sq=1, no extras — the scatter-written token is already in
    the pool), chunk (R=1, Sq=C, extras=the chunk itself), mixed (R=T, Sq=1,
    extras=the flattened step's K/V with the (T, T) same-slot position mask).

    ``row_map`` switches the block-table walk to VIRTUAL-REGION addressing
    for sequence-sharded pools: the pools are then an exchange buffer of
    per-region blocks in table order (region r's block j at pool row
    ``row_map[r] * nb + j`` — the result of resolving each global block id
    to its (owning shard, local slot) and exchanging exactly those blocks),
    and the walk streams regions instead of following table ids. With
    ``row_map=None`` (replicated pools) the walk follows ``tables`` ids —
    bit-identical geometry either way, so the two modes share one body.
    """
    R, Sq, q_dim = q.shape
    nb = tables.shape[1]
    if spec is None:
        bs, kv_dim = pool_k.shape[1], pool_k.shape[2]
    else:
        bs = pool_k.payload.shape[1]
        kv_dim = pool_k.payload.shape[-1] * 8 // spec.elem.bits
    hd = kv_dim // kv_heads
    H = q_dim // hd
    G = H // kv_heads
    SqG = Sq * G
    has_extra = k_extra is not None

    has_rm = row_map is not None

    # index maps take (grid indices..., *scalar-prefetch refs); pool-block
    # specs index the pool by the row's table entry (replicated pools) or by
    # its virtual region (sharded exchange buffer) — one block DMA per step
    if has_rm:
        def _row(r, j, tbl, hl, rm):
            return (r, 0, 0)

        def _blk(r, j, tbl, hl, rm):
            return (rm[r] * nb + j, 0, 0)

        def _whole(r, j, tbl, hl, rm):
            return (0, 0)
    else:
        def _row(r, j, tbl, hl):
            return (r, 0, 0)

        def _blk(r, j, tbl, hl):
            return (tbl[r, j], 0, 0)

        def _whole(r, j, tbl, hl):
            return (0, 0)

    def _row4(*idx):
        return _row(*idx) + (0,)

    # every block's last two dims are whole array dims or (8, 128)
    # multiples, as Mosaic requires: queries arrive pre-grouped per KV head
    # as (R, KV, Sq*G, hd), their positions as a (Sq*G, 1) column per row,
    # the extras' positions as a (1, E) row per row
    qg = q.reshape(R, Sq, kv_heads, G, hd).transpose(0, 2, 1, 3, 4)
    in_specs = [pl.BlockSpec((1, kv_heads, SqG, hd), _row4)]
    operands = [qg.reshape(R, kv_heads, SqG, hd)]
    if spec is None:
        in_specs += [pl.BlockSpec((1, bs, kv_dim), _blk),
                     pl.BlockSpec((1, bs, kv_dim), _blk)]
        operands += [pool_k, pool_v]
    else:
        pb, sb = pool_k.payload.shape[-1], pool_k.scales.shape[-1]
        in_specs += [pl.BlockSpec((1, bs, pb), _blk),
                     pl.BlockSpec((1, bs, sb), _blk),
                     pl.BlockSpec((1, bs, pb), _blk),
                     pl.BlockSpec((1, bs, sb), _blk)]
        operands += [pool_k.payload, pool_k.scales,
                     pool_v.payload, pool_v.scales]
    in_specs.append(pl.BlockSpec((1, SqG, 1), _row))
    operands.append(jnp.repeat(q_pos.astype(jnp.int32), G, axis=1)[..., None])
    if has_extra:
        E = k_extra.shape[0]
        t_extra = jnp.broadcast_to(t_extra.astype(jnp.int32), (R, E))
        in_specs += [pl.BlockSpec((E, kv_dim), _whole),
                     pl.BlockSpec((E, kv_dim), _whole),
                     pl.BlockSpec((1, 1, E), _row)]
        operands += [k_extra, v_extra, t_extra[:, None, :]]

    kernel = functools.partial(
        _kernel, spec=spec, kv_heads=kv_heads, head_dim=hd, block_size=bs,
        n_blocks=nb, scale=scale, window=window, has_extra=has_extra,
        has_row_map=has_rm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 if has_rm else 2,
        grid=(R, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kv_heads, SqG, hd), _row4),
        scratch_shapes=[
            pltpu.VMEM((kv_heads, SqG, 1), jnp.float32),       # running max
            pltpu.VMEM((kv_heads, SqG, 1), jnp.float32),       # running denom
            pltpu.VMEM((kv_heads, SqG, hd), jnp.float32),      # accumulator
        ],
    )
    prefetch = (tables.astype(jnp.int32), hist_len.astype(jnp.int32))
    if has_rm:
        prefetch += (row_map.astype(jnp.int32),)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, kv_heads, SqG, hd), out_dtype),
        interpret=interpret,
        name="paged_attention",
    )(*prefetch, *operands)
    out = out.reshape(R, kv_heads, Sq, G, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(R, Sq, q_dim)
