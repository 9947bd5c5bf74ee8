"""Pallas TPU kernels: MX dequantization, plain and fused with the
post-all-gather shard reduction.

``mx_dequantize_2d``     payload+scales tile -> dense fp tile.
``dequant_reduce``       (N, ...) gathered shards -> sum over N in ONE VMEM
                         pass — the decompress+reduce epilogue of the paper's
                         Fig. 1b, fused so gathered payloads never round-trip
                         through HBM as fp tensors.

Everything that moves values between lanes runs on the MXU as an exact
one-hot matmul (integers <= 255 and powers of two are exact in bfloat16):
spreading each payload byte over the codes it packs, and each block's scale
byte over the block's values. Byte and code arithmetic stays in int32 (the
TPU has no uint8 -> float32 convert), code values come from a static select
chain over the (<= 255 entry) code table, and scale factors are built
directly in the float32 exponent field — no gathers, no lane reshapes.

Widths that divide 8 (the paper's FP4 E2M1 among them) move whole codes
per matmul; 3-, 5- and 6-bit codes straddle bytes and move bit by bit.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.formats import MXSpec

__all__ = ["mx_dequantize_2d", "dequant_reduce", "row_tile"]

# VMEM rows per grid step are sized so one float32 row tile stays near this
_TILE_BYTES = 2 * 1024 * 1024


def row_tile(m: int, n: int) -> int:
    """Rows per grid step for an (m, n) codec call: all of ``m`` when it
    fits the VMEM budget (a block equal to the array is always legal),
    else the largest power-of-two multiple of 32 (the uint8 sublane tile)
    within budget that divides ``m``, else that budget row count with the
    caller padding ``m`` up to a multiple of it."""
    cap = 32
    while cap < 256 and 2 * cap * n * 4 <= _TILE_BYTES:
        cap *= 2
    if m <= cap:
        return m
    bm = cap
    while bm > 32 and m % bm:
        bm //= 2
    return bm if m % bm == 0 else cap


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _onehot_dot(x, mat):
    """Exact x @ mat for small non-negative integer (or power-of-two) x and
    a 0/1 or power-of-two ``mat``: single bf16 MXU pass, fp32 accumulate."""
    return jnp.dot(x.astype(jnp.bfloat16), mat.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def lane_chunks(n: int, width: int):
    """Static (start, size) lane chunks of a row of ``n`` values."""
    return [(s, min(width, n - s)) for s in range(0, n, width)]


def codes_per_chunk(bits: int) -> int:
    """Codes handled per one-hot matmul: the fewest whose packed bytes fill
    whole 128-lane tiles, so every payload slice starts on a lane tile."""
    return 1024 // math.gcd(bits, 8)


def _unpack_codes(payload, bits: int, n: int):
    """(rows, n*bits/8) uint8 payload -> (rows, n) int32 codes. Bit ``t``
    of code ``i`` is bit ``(i*bits + t) % 8`` of byte ``(i*bits + t) // 8``
    (the LSB-first stream of ``core.packing``)."""
    p = payload.astype(jnp.int32)
    if bits == 8:
        return p
    parts = []
    for s, w in lane_chunks(n, codes_per_chunk(bits)):
        nb = w * bits // 8
        chunk = p[:, s * bits // 8:s * bits // 8 + nb]
        byte, code = _iota((nb, w), 0), _iota((nb, w), 1)
        if 8 % bits == 0:
            # whole codes: copy each code's byte over it, then shift it out
            rep = _onehot_dot(chunk, code * bits // 8 == byte)
            shift = (_iota(rep.shape, 1) * bits) % 8
            parts.append((rep.astype(jnp.int32) >> shift) & ((1 << bits) - 1))
            continue
        acc = jnp.zeros((p.shape[0], w), jnp.float32)
        for r in range(8):  # stream bit 8*byte + r feeds one code bit
            pos = 8 * byte + r
            place = jnp.where(pos // bits == code,
                              (1 << (pos % bits)).astype(jnp.float32), 0.0)
            acc = acc + _onehot_dot((chunk >> r) & 1, place)
        parts.append(acc.astype(jnp.int32))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _values_from_codes(codes, spec: MXSpec):
    val = jnp.zeros(codes.shape, jnp.float32)
    for i, v in enumerate(spec.elem.code_values.tolist()):  # static
        val = jnp.where(codes == i, jnp.float32(v), val)
    return val


def exp2_int(e):
    """2.0 ** e for int32 ``e``, built in the float32 exponent field (exact;
    0 below the normal range, inf above it)."""
    field = e + 127
    f = jax.lax.bitcast_convert_type(jnp.clip(field, 0, 255) << 23,
                                     jnp.float32)
    return jnp.where(field > 0, f, 0.0)


def _dequant_tile(payload, scales, spec: MXSpec):
    """(rows, n*bits/8) payload + (rows, n/block) scale bytes -> (rows, n)
    float32 values."""
    n = payload.shape[-1] * 8 // spec.elem.bits
    blk, nblk = spec.block_size, scales.shape[-1]
    vals = _values_from_codes(_unpack_codes(payload, spec.elem.bits, n), spec)
    raw = scales.astype(jnp.int32)
    parts = []
    for s, w in lane_chunks(n, codes_per_chunk(spec.elem.bits)):
        own = (_iota((nblk, w), 1) + s) // blk == _iota((nblk, w), 0)
        parts.append(_onehot_dot(raw, own).astype(jnp.int32))  # byte per value
    raw_v = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return vals * exp2_int(raw_v - spec.scale.bias)


def _dequant_kernel(payload_ref, scales_ref, out_ref, *, spec: MXSpec):
    out_ref[...] = _dequant_tile(payload_ref[...], scales_ref[...], spec).astype(
        out_ref.dtype
    )


def _dequant_reduce_kernel(payload_ref, scales_ref, out_ref, *, spec: MXSpec):
    n_shards = payload_ref.shape[0]
    acc = _dequant_tile(payload_ref[0], scales_ref[0], spec)
    for s in range(1, n_shards):  # static unroll over TP degree
        acc = acc + _dequant_tile(payload_ref[s], scales_ref[s], spec)
    out_ref[...] = acc.astype(out_ref.dtype)


def _pad_rows(x, axis: int, rows: int):
    if x.shape[axis] == rows:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rows - x.shape[axis])
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("spec", "out_dtype", "interpret"))
def mx_dequantize_2d(
    payload: jnp.ndarray,
    scales: jnp.ndarray,
    spec: MXSpec,
    *,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    """(M, n_bytes) + (M, n_blocks) -> (M, N)."""
    m = payload.shape[0]
    n = payload.shape[1] * 8 // spec.elem.bits
    bm = row_tile(m, n)
    mp = -(-m // bm) * bm
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, spec=spec),
        grid=(mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, payload.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((bm, scales.shape[1]), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, n), out_dtype),
        interpret=interpret,
        name="mx_dequantize",
    )(_pad_rows(payload, 0, mp), _pad_rows(scales, 0, mp))
    return out[:m]


@functools.partial(jax.jit, static_argnames=("spec", "out_dtype", "interpret"))
def dequant_reduce(
    payload: jnp.ndarray,
    scales: jnp.ndarray,
    spec: MXSpec,
    *,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    """(S, M, n_bytes) + (S, M, n_blocks) -> (M, N): dequantize the S gathered
    shards and reduce, one VMEM pass."""
    s, m, nbytes = payload.shape
    n = nbytes * 8 // spec.elem.bits
    bm = row_tile(m, n * max(1, s // 2))
    mp = -(-m // bm) * bm
    out = pl.pallas_call(
        functools.partial(_dequant_reduce_kernel, spec=spec),
        grid=(mp // bm,),
        in_specs=[
            pl.BlockSpec((s, bm, nbytes), lambda i: (0, i, 0)),
            pl.BlockSpec((s, bm, scales.shape[-1]), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, n), out_dtype),
        interpret=interpret,
        name="mx_dequantize_reduce",
    )(_pad_rows(payload, 1, mp), _pad_rows(scales, 1, mp))
    return out[:m]
