"""jit'd public wrappers for the MX codec kernels.

These are drop-in replacements for repro.core.mx.{quantize,dequantize} used
by the compressed collectives when ``policy.use_pallas`` is set. Arbitrary
leading dims are flattened to 2-D for the kernels; shapes that don't satisfy
the tiling constraints fall back to the pure-jnp oracle (never wrong, just
not the fast path).

On the CPU backend kernels run with interpret=True; on TPU they lower to
Mosaic (interpret=False).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.formats import MXSpec
from repro.core.mx import MXCompressed
from repro.core import mx as _oracle
from repro.kernels.mx_dequant import dequant_reduce, mx_dequantize_2d
from repro.kernels.mx_quant import mx_quantize_2d

__all__ = ["mx_quantize", "mx_dequantize", "mx_dequant_reduce", "interpret_mode"]


def interpret_mode() -> bool:
    """Run Pallas kernels in interpret mode: on the CPU backend only."""
    return jax.default_backend() == "cpu"


def _can_tile(n: int, spec: MXSpec) -> bool:
    return n % spec.block_size == 0 and (n * spec.elem.bits) % 8 == 0 and n % 8 == 0


def mx_quantize(x: jnp.ndarray, spec: MXSpec) -> MXCompressed:
    lead, n = x.shape[:-1], x.shape[-1]
    m = 1
    for d in lead:
        m *= int(d)
    if m == 0 or not _can_tile(n, spec):
        return _oracle.quantize(x, spec)
    payload, scales = mx_quantize_2d(
        x.reshape(m, n), spec, interpret=interpret_mode()
    )
    return MXCompressed(
        payload=payload.reshape(*lead, payload.shape[-1]),
        scales=scales.reshape(*lead, scales.shape[-1]),
    )


def mx_dequantize(comp: MXCompressed, spec: MXSpec, out_dtype=jnp.float32) -> jnp.ndarray:
    lead = comp.payload.shape[:-1]
    nbytes = comp.payload.shape[-1]
    n = nbytes * 8 // spec.elem.bits
    m = 1
    for d in lead:
        m *= int(d)
    if m == 0 or not _can_tile(n, spec):
        return _oracle.dequantize(comp, spec, out_dtype)
    out = mx_dequantize_2d(
        comp.payload.reshape(m, nbytes),
        comp.scales.reshape(m, comp.scales.shape[-1]),
        spec,
        out_dtype=out_dtype,
        interpret=interpret_mode(),
    )
    return out.reshape(*lead, n)


def mx_dequant_reduce(comp: MXCompressed, spec: MXSpec, out_dtype=jnp.float32) -> jnp.ndarray:
    """Fused decompress+sum over the leading (gathered shards) axis."""
    s = comp.payload.shape[0]
    lead = comp.payload.shape[1:-1]
    nbytes = comp.payload.shape[-1]
    n = nbytes * 8 // spec.elem.bits
    m = 1
    for d in lead:
        m *= int(d)
    if m == 0 or not _can_tile(n, spec):
        vals = _oracle.dequantize(comp, spec, jnp.float32)
        return jnp.sum(vals, axis=0).astype(out_dtype)
    out = dequant_reduce(
        comp.payload.reshape(s, m, nbytes),
        comp.scales.reshape(s, m, comp.scales.shape[-1]),
        spec,
        out_dtype=out_dtype,
        interpret=interpret_mode(),
    )
    return out.reshape(*lead, n)
