"""Pallas TPU kernel: MX block quantization (the compress half of the codec).

The kernel tiles the (tokens, features) activation into whole-row VMEM
blocks and keeps every step in that (rows, features) layout:

* per-MX-block amax by a lane butterfly (``pltpu.roll`` partners at
  distance 1, 2, 4, ... inside each block), so every lane holds its block's
  amax without reshaping lanes into blocks;
* shared exponents via fp32 exponent-field extraction (bit-exact with the
  core oracle), scale factors built in the exponent field;
* round-to-nearest onto the element format's code table with a vectorized
  midpoint compare-sum (static compares — no gather/searchsorted);
* packing as exact one-hot MXU matmuls: codes times their power-of-two
  place weight summed into bytes, and each block's scale byte picked out of
  its first lane.

Outputs per input tile (bm, n):
  payload (bm, n * bits // 8) uint8   — packed codes
  scales  (bm, n // block)    uint8   — raw-biased shared exponents
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import MXSpec
from repro.kernels.mx_dequant import (
    _iota, _onehot_dot, _pad_rows, codes_per_chunk, exp2_int, lane_chunks,
    row_tile,
)

__all__ = ["mx_quantize_2d", "quant_block_shapes"]


def _block_max(a, blk: int):
    """Max over each aligned group of ``blk`` lanes, broadcast back to every
    lane of the group (butterfly: partner lane = lane XOR step). The partner
    is chosen by where each rolled lane came from, so the result does not
    depend on the rotation's direction convention."""
    n = a.shape[-1]
    lane = _iota(a.shape, 1)
    step = 1
    while step < blk:
        fwd, back = pltpu.roll(a, step, 1), pltpu.roll(a, n - step, 1)
        src = pltpu.roll(lane, step, 1)
        a = jnp.maximum(a, jnp.where(src == (lane ^ step), fwd, back))
        step *= 2
    return a


def _quant_kernel(x_ref, payload_ref, scales_ref, *, spec: MXSpec):
    x = x_ref[...].astype(jnp.float32)
    n = x.shape[-1]
    blk, bits = spec.block_size, spec.elem.bits

    # shared exponent: exact floor(log2(amax)) via the exponent field
    amax = _block_max(jnp.abs(x), blk)
    ebits = jax.lax.bitcast_convert_type(amax, jnp.int32)
    e = ((ebits >> 23) & 0xFF) - 127 - spec.elem.emax
    e = jnp.where(amax > 0, e, spec.scale.min_exp)
    e = jnp.clip(e, spec.scale.min_exp, spec.scale.max_exp)

    # round-to-nearest onto the code table (midpoint compare-sum)
    norm = x * exp2_int(-e)
    idx = jnp.zeros(x.shape, jnp.int32)
    for m in spec.elem.midpoints.tolist():  # static python loop
        idx += (norm > jnp.float32(m)).astype(jnp.int32)

    # scale byte of each block = its first lane's biased exponent
    raw = e + spec.scale.bias
    nblk = n // blk
    acc = jnp.zeros((x.shape[0], nblk), jnp.float32)
    for s, w in lane_chunks(n, codes_per_chunk(bits)):
        first = (_iota((w, nblk), 0) + s) == _iota((w, nblk), 1) * blk
        acc = acc + _onehot_dot(raw[:, s:s + w], first)
    scales_ref[...] = acc.astype(jnp.int32).astype(jnp.uint8)

    if bits == 8:
        payload_ref[...] = idx.astype(jnp.uint8)
        return
    # codes -> bytes: bit t of code i is stream bit i*bits + t (LSB first,
    # core.packing's layout); widths dividing 8 move whole codes at once
    planes = ([(idx, 0)] if 8 % bits == 0 else
              [((idx >> t) & 1, t) for t in range(bits)])
    for s, w in lane_chunks(n, codes_per_chunk(bits)):
        nb = w * bits // 8
        code, byte = _iota((w, nb), 0), _iota((w, nb), 1)
        packed = jnp.zeros((x.shape[0], nb), jnp.float32)
        for plane, t in planes:
            pos = code * bits + t
            place = jnp.where(pos // 8 == byte,
                              (1 << (pos % 8)).astype(jnp.float32), 0.0)
            packed = packed + _onehot_dot(plane[:, s:s + w], place)
        lo = s * bits // 8
        payload_ref[:, lo:lo + nb] = packed.astype(jnp.int32).astype(jnp.uint8)


def quant_block_shapes(m: int, n: int, spec: MXSpec):
    """(bm, bn) VMEM tile of the quantize grid: whole rows (the scale bytes
    of a row must land in one block) and ``row_tile`` rows."""
    del spec
    return row_tile(m, n), n


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def mx_quantize_2d(
    x: jnp.ndarray,
    spec: MXSpec,
    *,
    interpret: bool = False,
):
    """Quantize a 2-D (M, N) array. N % block == 0, N % 8 == 0 required."""
    m, n = x.shape
    bm, _ = quant_block_shapes(m, n, spec)
    mp = -(-m // bm) * bm
    bits = spec.elem.bits
    payload, scales = pl.pallas_call(
        functools.partial(_quant_kernel, spec=spec),
        grid=(mp // bm,),
        in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((bm, n * bits // 8), lambda i: (i, 0)),
            pl.BlockSpec((bm, n // spec.block_size), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((mp, n * bits // 8), jnp.uint8),
            jax.ShapeDtypeStruct((mp, n // spec.block_size), jnp.uint8),
        ),
        interpret=interpret,
        name="mx_quantize",
    )(_pad_rows(x, 0, mp))
    return payload[:m], scales[:m]
