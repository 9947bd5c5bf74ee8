"""Compressed collectives — the paper's contribution as jax.lax primitives.

``compressed_psum`` implements Fig. 1b: quantize the local partial sum with an
MX scheme, all-gather the *compressed* payload (bit-packed codes + one scale
byte per block), dequantize all shards locally and reduce with a sum.

All functions here run *inside* shard_map-manual code (they take an
``axis_name``). The TP-island wrappers that embed them into a GSPMD program
live in ``repro.core.tp``.

Every MX quantize and dequantize of a collective here, jnp or Pallas, runs
under the ``tp_codec`` named scope, so a device trace credits the codec's
time to one name; the collectives themselves stay outside it.
"""
from __future__ import annotations

import functools
import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import mx
from repro.core.formats import MXSpec
from repro.core.mx import MXCompressed
from repro.core.policy import CompressionPolicy

__all__ = [
    "compressed_psum",
    "compressed_all_gather",
    "compressed_all_to_all",
    "masked_owner_psum",
    "psum_maybe_compressed",
    "reset_downgrade_warnings",
]


def masked_owner_psum(
    x: jnp.ndarray, own: jnp.ndarray, axis_name: str
) -> jnp.ndarray:
    """Bit-exact ownership select across a mesh axis.

    Every shard contributes ``x`` rows it owns and zeros elsewhere; the psum
    reconstructs the full tensor on every shard. ``own`` is a boolean mask
    broadcastable to ``x`` that must be True on EXACTLY ONE shard per element
    — then each summand has a single nonzero contributor and the reduction is
    exact. Float payloads are masked and reduced in the same-width unsigned
    integer domain (bitcast round-trip), so bf16/fp32 pool blocks and uint8
    wire bytes all survive the exchange bit-for-bit: the sequence-sharded
    pool read path moves only table-named blocks in wire format and stays
    bit-identical to a replicated pool.
    """
    dt = jnp.dtype(x.dtype)
    if dt.kind == "f":
        u = {2: jnp.uint16, 4: jnp.uint32}[dt.itemsize]
        xi = lax.bitcast_convert_type(x, u)
    else:
        xi = x
    xi = jnp.where(own, xi, jnp.zeros((), xi.dtype))
    tot = lax.psum(xi, axis_name)
    return lax.bitcast_convert_type(tot, dt) if dt.kind == "f" else tot


_DOWNGRADE_WARNED: set = set()


def reset_downgrade_warnings() -> None:
    """Forget which two_phase downgrades have already warned (tests, or a
    fresh serving process reusing a long-lived interpreter)."""
    _DOWNGRADE_WARNED.clear()


def _variant_downgrade(reason: str, strict: bool, key: tuple = ()) -> None:
    """A requested two_phase reduction cannot run; raise under ``strict`` or
    warn once per distinct (reason, spec, shape, axis) site — NOT once per
    process: a second engine with a different policy or feature dim gets its
    own warning rather than having its downgrade masked by an earlier
    engine's (trace-time Python, so the set lookup is cheap)."""
    msg = (
        f"compressed_psum: variant='two_phase' requested but {reason}; "
        "falling back to the gather variant. Plumb axis_size (the TP degree) "
        "and ensure the feature dim is divisible by axis_size * block_size, "
        "or set strict=False/strict_variant=False to accept the fallback."
    )
    if strict:
        raise ValueError(msg)
    dedup = (reason,) + key
    if dedup not in _DOWNGRADE_WARNED:
        _DOWNGRADE_WARNED.add(dedup)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _overlap_chunks(f: int, spec: MXSpec, requested: int) -> int:
    """Largest chunk count <= ``requested`` that splits a feature dim of
    ``f`` into equal block-aligned chunks.

    MX quantization is per-block independent, so any block-aligned split
    produces bit-identical codes to the unchunked codec — chunking changes
    the schedule (quantize/transmit overlap), never the values. Degrades to
    1 (unchunked) rather than erroring when ``f`` doesn't divide."""
    n = max(1, int(requested))
    while n > 1 and (f % n != 0 or (f // n) % spec.block_size != 0):
        n -= 1
    return n


def _quantize_staged(x: jnp.ndarray, spec: MXSpec, quantize, n_chunks: int):
    """Stage 1 of the two-stage pipeline: quantize every feature chunk
    before any collective is issued, so XLA's async all-gather can overlap
    chunk k's transfer with chunk k+1's (already traced) quantize work."""
    if n_chunks == 1:
        return [x], [quantize(x, spec)]
    chunks = jnp.split(x, n_chunks, axis=-1)
    return chunks, [quantize(c, spec) for c in chunks]


def _gather_staged(comps, axis_name: str):
    """Stage 2: issue every chunk's payload+scales all-gather pair, in
    payload-then-scales order per chunk (the static auditor pairs uint8
    collectives by equation order)."""
    return [
        (lax.all_gather(c.payload, axis_name), lax.all_gather(c.scales, axis_name))
        for c in comps
    ]


TP_CODEC_SCOPE = "tp_codec"


def _in_codec_scope(fn):
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(TP_CODEC_SCOPE):
            return fn(*args, **kwargs)
    return scoped


def _codec(use_pallas: bool):
    """Return (quantize, dequantize) implementations, each under the
    ``tp_codec`` named scope.

    The Pallas kernels are drop-in replacements for the pure-jnp codec with
    identical semantics (tests assert bit-exactness). On CPU we run them in
    interpret mode; on TPU they compile to Mosaic.
    """
    if use_pallas:
        from repro.kernels import ops  # local import: kernels are optional

        return (_in_codec_scope(ops.mx_quantize),
                _in_codec_scope(ops.mx_dequantize))
    return _in_codec_scope(mx.quantize), _in_codec_scope(mx.dequantize)


def compressed_all_gather(
    x: jnp.ndarray,
    axis_name: str,
    spec: MXSpec,
    *,
    use_pallas: bool = False,
    overlap_chunks: int = 1,
) -> jnp.ndarray:
    """All-gather ``x`` (leading axis stacked) in compressed form.

    Returns the dequantized gathered tensor of shape (axis_size, *x.shape).

    overlap_chunks > 1 selects the chunked two-stage variant (Flash
    Communication, arxiv 2412.04964): the feature dim is split into
    block-aligned chunks, every chunk is quantized up front, then the
    per-chunk gathers are issued back to back so the transfer of chunk k
    overlaps the quantize/dequantize compute of its neighbours. Chunking is
    bit-identical to the unchunked codec (MX blocks are independent) and
    degrades to 1 when the feature dim doesn't split evenly.
    """
    quantize, dequantize = _codec(use_pallas)
    n_chunks = _overlap_chunks(x.shape[-1], spec, overlap_chunks)
    chunks, comps = _quantize_staged(x, spec, quantize, n_chunks)
    wires = _gather_staged(comps, axis_name)
    outs = [
        dequantize(MXCompressed(payload, scales), spec)
        for payload, scales in wires
    ]
    out = outs[0] if n_chunks == 1 else jnp.concatenate(outs, axis=-1)
    return out.astype(x.dtype)


def _gathered_reduce(
    payload: jnp.ndarray,
    scales: jnp.ndarray,
    comp: MXCompressed,
    chunk: jnp.ndarray,
    spec: MXSpec,
    use_pallas: bool,
    keep_local_fp: bool,
    accum_dtype,
    dequantize,
) -> jnp.ndarray:
    """Reduce one chunk's gathered (N-stacked) wire pair to its total."""
    if use_pallas:
        # fused decompress+sum epilogue: one VMEM pass over the shards
        from repro.kernels import ops

        with jax.named_scope(TP_CODEC_SCOPE):
            total = ops.mx_dequant_reduce(MXCompressed(payload, scales),
                                          spec, out_dtype=accum_dtype)
    else:
        # stream the shard accumulation — materializing the dequantized
        # (N, ..., F) fp32 tensor at once would dwarf the activation memory
        n = payload.shape[0]

        def body(i, acc):
            sh = dequantize(
                MXCompressed(payload[i], scales[i]), spec
            ).astype(accum_dtype)
            return acc + sh

        total = lax.fori_loop(0, n, body, jnp.zeros(chunk.shape, accum_dtype))
    if keep_local_fp:
        own_q = dequantize(comp, spec).astype(accum_dtype)
        total = total - own_q + chunk.astype(accum_dtype)
    return total


def _compressed_psum_fwd(
    partial: jnp.ndarray,
    axis_name: str,
    spec: MXSpec,
    use_pallas: bool,
    keep_local_fp: bool,
    accum_dtype,
    overlap_chunks: int = 1,
) -> jnp.ndarray:
    quantize, dequantize = _codec(use_pallas)
    n_chunks = _overlap_chunks(partial.shape[-1], spec, overlap_chunks)
    chunks, comps = _quantize_staged(partial, spec, quantize, n_chunks)
    wires = _gather_staged(comps, axis_name)
    totals = [
        _gathered_reduce(payload, scales, comp, chunk, spec, use_pallas,
                         keep_local_fp, accum_dtype, dequantize)
        for (payload, scales), comp, chunk in zip(wires, comps, chunks)
    ]
    total = totals[0] if n_chunks == 1 else jnp.concatenate(totals, axis=-1)
    return total.astype(partial.dtype)


def compressed_psum(
    partial: jnp.ndarray,
    axis_name: str,
    spec: MXSpec,
    *,
    use_pallas: bool = False,
    keep_local_fp: bool = False,
    accum_dtype=jnp.float32,
    variant: str = "gather",
    axis_size: int = 0,
    strict: bool = False,
    overlap_chunks: int = 1,
) -> jnp.ndarray:
    """The paper's compressed reduction for row-parallel TP layers.

    partial: this worker's partial sum, shape (..., F) with F % block == 0.
    Equivalent to ``lax.psum(partial, axis_name)`` up to quantization error,
    but communicates ~(16 / effective_bits)x fewer bytes.

    keep_local_fp: dequantize only the remote shards and add the local shard
    in full precision (matches the paper's §4.3 wording). Slightly better
    accuracy; output then differs per worker by each worker's own
    quantization residual. Default False => bit-identical replicated output.

    Gradient: straight-through estimator. d(sum_i partial_i)/d(partial_i) is
    the identity, so the backward pass returns the (replicated) output
    cotangent directly — the quantizer's zero-measure jumps are skipped, and
    no backward collective is needed. (The paper is inference-only; STE makes
    the train_4k shapes train correctly with compression enabled.)

    overlap_chunks: feature-dim chunk count for the gather variant's
    two-stage quantize/transmit pipeline (see ``compressed_all_gather``).
    The two_phase variant already splits features per destination and is
    left unchunked.
    """
    use_two_phase = (
        variant == "two_phase"
        and axis_size > 1
        and partial.shape[-1] % (axis_size * spec.block_size) == 0
    )
    if variant == "two_phase" and not use_two_phase:
        # dedup key carries the site identity (policy spec, wire shape, TP
        # degree): one engine's downgrade can never mask another's
        key = (spec.name, partial.shape[-1], axis_size)
        if axis_size <= 1:
            _variant_downgrade(
                f"axis_size={axis_size} is not plumbed (need the TP degree)",
                strict, key)
        else:
            _variant_downgrade(
                f"feature dim {partial.shape[-1]} is not divisible by "
                f"axis_size * block_size = {axis_size * spec.block_size}",
                strict, key)

    @jax.custom_vjp
    def _psum(p):
        if use_two_phase:
            return _compressed_psum_two_phase(p, axis_name, spec, use_pallas,
                                              accum_dtype)
        return _compressed_psum_fwd(p, axis_name, spec, use_pallas,
                                    keep_local_fp, accum_dtype,
                                    overlap_chunks=overlap_chunks)

    def _fwd(p):
        return _psum(p), None

    def _bwd(_, g):
        return (g.astype(partial.dtype),)

    _psum.defvjp(_fwd, _bwd)
    return _psum(partial)


def _compressed_psum_two_phase(
    partial: jnp.ndarray,
    axis_name: str,
    spec: MXSpec,
    use_pallas: bool,
    accum_dtype,
) -> jnp.ndarray:
    """Beyond-paper compressed reduction: quantized reduce-scatter (via
    all-to-all of per-destination feature chunks) followed by a quantized
    all-gather of the reduced slices.

    Communication: ~2x compressed tensor bytes per device, vs the paper's
    gather scheme at N x compressed bytes — at TP degree N > ~2*16/eff_bits
    the gather scheme moves MORE bytes than an uncompressed ring all-reduce;
    this variant stays ~eff_bits/32 x below the ring regardless of N.
    Cost: the values are quantized twice (partials + reduced slices), so the
    quantization error is ~sqrt(2) x the gather variant's (measured in
    benchmarks/table1 variants sweep).
    """
    quantize, dequantize = _codec(use_pallas)
    n = jax.lax.psum(1, axis_name)  # static under shard_map tracing
    n = int(n)
    f = partial.shape[-1]
    lead = partial.shape[:-1]
    # split features into N destination slices: (..., N, F/N)
    chunks = partial.reshape(*lead, n, f // n)
    chunks = jnp.moveaxis(chunks, -2, 0)                  # (N, ..., F/N)
    comp = quantize(chunks, spec)
    payload = lax.all_to_all(comp.payload, axis_name, 0, 0)
    scales = lax.all_to_all(comp.scales, axis_name, 0, 0)
    vals = dequantize(MXCompressed(payload, scales), spec)  # (N, ..., F/N)
    my_slice = jnp.sum(vals.astype(accum_dtype), axis=0)    # reduced slice
    # phase 2: compressed all-gather of the reduced slice
    comp2 = quantize(my_slice.astype(partial.dtype), spec)
    payload2 = lax.all_gather(comp2.payload, axis_name)
    scales2 = lax.all_gather(comp2.scales, axis_name)
    slices = dequantize(MXCompressed(payload2, scales2), spec)  # (N, ..., F/N)
    out = jnp.moveaxis(slices, 0, -2).reshape(*lead, f)
    return out.astype(partial.dtype)


def compressed_all_to_all(
    x: jnp.ndarray,
    axis_name: str,
    spec: MXSpec,
    *,
    split_axis: int,
    concat_axis: int,
    use_pallas: bool = False,
) -> jnp.ndarray:
    """Compressed MoE dispatch/combine all-to-all (beyond-paper extension).

    Quantizes along the last axis, all-to-alls payload+scales, dequantizes.
    Requires the last axis to be the feature axis (not split/concat).
    """
    ndim = x.ndim
    assert split_axis != ndim - 1 and concat_axis != ndim - 1, (
        "feature (last) axis must not be the split/concat axis"
    )
    quantize, dequantize = _codec(use_pallas)
    comp = quantize(x, spec)
    payload = lax.all_to_all(comp.payload, axis_name, split_axis, concat_axis)
    scales = lax.all_to_all(comp.scales, axis_name, split_axis, concat_axis)
    return dequantize(MXCompressed(payload, scales), spec).astype(x.dtype)


def psum_maybe_compressed(
    partial: jnp.ndarray,
    axis_name: str,
    policy: Optional[CompressionPolicy],
    *,
    n_tokens: Optional[int] = None,
    axis_size: int = 0,
) -> jnp.ndarray:
    """Policy-gated reduction: the single entry point model code uses.

    n_tokens defaults to the product of all but the last dim (the number of
    activations rows crossing the wire) — the prefill/decode discriminator.
    """
    if n_tokens is None:
        # static Python shape math: shapes are known at trace time, and the
        # jnp round-trip would materialize a traced array inside jit
        n_tokens = math.prod(partial.shape[:-1]) if partial.ndim > 1 else 1
    if policy is None or not policy.active_for(n_tokens):
        return lax.psum(partial, axis_name)
    return compressed_psum(
        partial,
        axis_name,
        policy.spec,
        use_pallas=policy.use_pallas,
        keep_local_fp=policy.keep_local_fp,
        accum_dtype=jnp.dtype(policy.accum_dtype),
        variant=policy.variant,
        axis_size=axis_size,
        strict=policy.strict_variant,
        overlap_chunks=policy.overlap_chunks,
    )
