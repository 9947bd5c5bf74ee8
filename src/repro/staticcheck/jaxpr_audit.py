"""Jaxpr auditor: walk every compiled engine program and machine-check the
communication contract the paper's results rest on.

The collectives this repo cares about are all issued *inside* shard_map
islands (``repro.core.tp``), so they appear verbatim in the traced jaxpr as
``psum`` / ``all_gather`` / ``all_to_all`` / ``ppermute`` eqns nested under
the island's call eqn — unlike GSPMD-inserted collectives, which only
materialize after partitioning. That makes the contract statically
checkable: trace (never execute) each program via ``Engine.trace_programs``,
recurse through every sub-jaxpr, and inventory what crosses the mesh.

Rules (each owns a ``Finding.rule`` id; DESIGN.md §Static analysis):

- ``dense-collective`` — a float-dtype collective over the TP axis inside a
  program whose active ``CompressionPolicy`` says the boundary is
  compressed. This is the failure mode the ROADMAP warns about (a dense
  bf16 all-gather silently reappearing in the hot path).
- ``wire-shape`` — compressed traffic must be uint8 payload+scale pairs
  whose shapes match ``wire_arrays_shape`` for the policy's spec.
- ``missing-compression`` — the inverse of ``dense-collective``: a program
  marked ``prefill_dominated`` (the gate variant the engine dispatches for
  prefill-heavy mixed steps) whose active policy compresses the boundary
  must actually CARRY uint8 wire traffic over the TP axis. The thesis has
  to be *present*, not merely not-violated — this is the rule that turns
  red when the mixed hot path silently regresses to dense collectives
  (the PR-5-era gap where the unified step ran under whatever ctx it was
  traced with and nobody noticed the compression was gone).
- ``dtype-drift`` — program boundaries hold their contract dtypes: logits
  come out at the model compute dtype (no silent f32/weak-type upcast
  escaping an fp4/bf16 path), the KV state pytree leaves the program with
  exactly the avals it entered with (pools never change storage format),
  and no float64 appears anywhere.
- ``host-transfer`` — no callback/infeed/outfeed eqns inside per-step
  programs (a hidden host round-trip per step would dominate step time).
- ``retrace-mismatch`` — tracing the program twice yields the same jaxpr,
  a necessary condition for the compile-once contract (a value-dependent
  trace would fan out compiled variants at run time).
- ``pool-gather`` — on a ``use_pallas`` engine, per-step programs must not
  gather a KV pool at full capacity through HBM (``pool[tables]``): the
  whole point of the gather-free kernel is that pool reads happen block-by
  -block inside the ``pallas_call``. Any ``gather`` eqn whose operand aval
  matches a pool leaf turns the audit red.
- ``pool-reshard`` — on a sequence-sharded-pool engine (``kv_shards > 1``),
  per-step programs must never materialize a full-capacity replicated pool:
  no ``all_gather`` over the kv axis with a pool-slab operand (that IS the
  replication the sharding exists to avoid — the legit exchange moves only
  table-named blocks via masked psum, so its operands are table-sized), and
  no ``gather`` over a full-pool aval (a replicated ``pool[tables]`` read
  can only exist if the pool was first reassembled).

Recursion covers ``pallas_call`` eqns too: their kernel jaxpr rides in
``eqn.params`` like any other call primitive (``_sub_jaxprs`` is
duck-typed), so a dense TP collective — or a pool gather — hidden inside a
kernel body is inventoried exactly like one in the surrounding program.
``tests/test_staticcheck.py`` pins this with mutation tests.

``audit_static_args`` is the jit-cache-key companion: it statically derives
every ``jax.jit``/``functools.partial(jax.jit, ...)`` site's static-arg
signature from the AST (shared with the lint pass) so the compile-once
claims the tests observe dynamically are also derived statically.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.mx import wire_arrays_shape
from repro.staticcheck.report import (
    AuditReport, CollectiveRecord, Finding, ProgramReport, ProgramTrace,
)

__all__ = [
    "COLLECTIVE_PRIMITIVES", "HOST_TRANSFER_PRIMITIVES",
    "iter_eqns", "collect_collectives", "audit_program", "audit_engine",
]

COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "pmax", "pmin", "all_gather", "all_to_all", "ppermute",
    "reduce_scatter", "psum_scatter",
})

# eqns that imply a host round-trip when they appear inside a step program
HOST_TRANSFER_PRIMITIVES = frozenset({
    "infeed", "outfeed", "host_local_array_to_global_array",
    "global_array_to_host_local_array", "device_put", "debug_print",
})


def _sub_jaxprs(params: Dict[str, Any]) -> Iterator[Any]:
    """Yield every jaxpr buried in an eqn's params (shard_map bodies,
    custom_vjp calls, pjit, scan/while/cond branches, ...) without naming
    the individual primitives — duck-typed so new call primitives keep
    auditing for free."""
    for v in params.values():
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(item, "eqns"):              # jax.core.Jaxpr
                yield item
            elif hasattr(item, "jaxpr"):           # jax.core.ClosedJaxpr
                yield item.jaxpr


def iter_eqns(jaxpr: Any) -> Iterator[Any]:
    """Depth-first, code-order iteration over every eqn of ``jaxpr`` and all
    nested sub-jaxprs. Accepts a Jaxpr or ClosedJaxpr."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _eqn_axes(eqn: Any) -> Tuple[str, ...]:
    """Mesh axis names a collective eqn runs over (normalized, strings only —
    positional axis indices can't be mesh axes)."""
    raw = eqn.params.get("axis_name", eqn.params.get("axes", ()))
    if not isinstance(raw, (tuple, list)):
        raw = (raw,)
    return tuple(a for a in raw if isinstance(a, str))


def collect_collectives(
    jaxpr: Any, axis_sizes: Optional[Dict[str, int]] = None,
) -> List[CollectiveRecord]:
    """Inventory every collective eqn reachable from ``jaxpr``."""
    axis_sizes = axis_sizes or {}
    records = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name not in COLLECTIVE_PRIMITIVES:
            continue
        axes = _eqn_axes(eqn)
        size = 1
        for a in axes:
            size *= axis_sizes.get(a, 1)
        for var in eqn.invars:
            aval = getattr(var, "aval", None)
            if aval is None or not hasattr(aval, "shape"):
                continue
            records.append(CollectiveRecord(
                primitive=eqn.primitive.name,
                axes=axes,
                dtype=str(aval.dtype),
                shape=tuple(aval.shape),
                bytes_per_device=int(aval.size) * aval.dtype.itemsize,
                axis_size=size,
                source=str(eqn.source_info.traceback.frames[0]
                           if getattr(eqn.source_info, "traceback", None)
                           else ""),
            ))
    return records


# ------------------------------------------------------------------- rules


def _is_float(dtype: str) -> bool:
    return dtype.startswith(("float", "bfloat"))


def _check_compressed_wire(trace: ProgramTrace, tp_records: List[CollectiveRecord],
                           findings: List[Finding]) -> None:
    """In a compressed program, TP traffic must be MX wire bytes: no dense
    float collectives, and the uint8 payload/scale pairs must match
    ``wire_arrays_shape`` for the active spec."""
    spec = trace.policy.spec
    for r in tp_records:
        if _is_float(r.dtype) and math.prod(r.shape or (1,)) > 1:
            findings.append(Finding(
                "dense-collective", trace.name,
                f"dense {r.dtype} {r.primitive} over {r.axes} with shape "
                f"{r.shape} in a program whose policy "
                f"({spec.name}, n_tokens={trace.n_tokens} >= "
                f"min_tokens={trace.policy.min_tokens}) compresses this "
                f"boundary"))
    # pair payload/scale gathers in eqn order: quantize emits payload then
    # scales, and both cross the wire back-to-back (collectives.py)
    u8 = [r for r in tp_records if r.dtype == "uint8"
          and r.primitive in ("all_gather", "all_to_all")]
    if not u8 and not any(_is_float(r.dtype) for r in tp_records) and tp_records:
        findings.append(Finding(
            "wire-shape", trace.name,
            f"compressed program has TP collectives but no uint8 wire "
            f"traffic: {[(r.primitive, r.dtype) for r in tp_records]}"))
    if len(u8) % 2:
        findings.append(Finding(
            "wire-shape", trace.name,
            f"odd number of uint8 collectives ({len(u8)}) — every payload "
            f"transfer must be paired with its scale transfer"))
        return
    for payload, scales in zip(u8[0::2], u8[1::2]):
        n_values = scales.shape[-1] * spec.block_size
        want_payload, want_scales = wire_arrays_shape(
            (*scales.shape[:-1], n_values), spec)
        if (tuple(payload.shape) != tuple(want_payload)
                or tuple(scales.shape) != tuple(want_scales)):
            findings.append(Finding(
                "wire-shape", trace.name,
                f"uint8 pair {payload.shape}/{scales.shape} does not match "
                f"wire_arrays_shape for {spec.name}: want "
                f"{want_payload}/{want_scales}"))


def _check_compression_present(trace: ProgramTrace,
                               tp_records: List[CollectiveRecord],
                               findings: List[Finding]) -> None:
    """Inverse rule: a prefill-dominated program under an active policy must
    put compressed bytes on the wire. ``dense-collective`` only fires when a
    dense float collective is *present*; this rule fires when the uint8 wire
    pair is *absent* — together they make the compression contract
    two-sided. Only applies when the program has TP collectives at all
    (mesh-less engines have nothing to compress)."""
    if not (trace.prefill_dominated and tp_records):
        return
    if any(r.dtype == "uint8" for r in tp_records):
        return
    findings.append(Finding(
        "missing-compression", trace.name,
        f"prefill-dominated program under active policy "
        f"({trace.policy.spec.name}, n_tokens={trace.n_tokens}) has TP "
        f"collectives {[(r.primitive, r.dtype) for r in tp_records]} but no "
        f"uint8 wire traffic — the paper's compressed collective is absent "
        f"from the hot path"))


def _aval_sig(tree: Any) -> List[Tuple[Tuple[int, ...], str]]:
    return [(tuple(l.shape), str(l.dtype)) for l in jax.tree_util.tree_leaves(tree)]


def _check_dtype_drift(trace: ProgramTrace, findings: List[Finding]) -> None:
    # logits leave the program at the model compute dtype — a silent fp32
    # upcast inside an fp4/bf16 path would surface here as f32 logits
    if trace.logits_out is not None:
        want = str(jnp.dtype(trace.compute_dtype))
        got = str(trace.logits_out.dtype)
        if got != want:
            findings.append(Finding(
                "dtype-drift", trace.name,
                f"logits dtype {got} != compute dtype {want} — an upcast "
                f"(or downcast) escaped the program boundary"))
    # the state pytree is a fixed-point: identical avals in and out, or the
    # donation/compile-once contract breaks and pools change storage format
    if trace.state_in is not None and trace.state_out is not None:
        sin, sout = _aval_sig(trace.state_in), _aval_sig(trace.state_out)
        if sin != sout:
            diff = [(a, b) for a, b in zip(sin, sout) if a != b][:4]
            findings.append(Finding(
                "dtype-drift", trace.name,
                f"state avals drift across the program: {len(sin)} in vs "
                f"{len(sout)} out leaves; first diffs {diff}"))
    for eqn in iter_eqns(trace.jaxpr):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and str(getattr(aval, "dtype", "")) == "float64":
                findings.append(Finding(
                    "dtype-drift", trace.name,
                    f"float64 intermediate produced by '{eqn.primitive.name}' "
                    f"— x64 must never enter a serving program"))
                return


def _check_host_transfer(trace: ProgramTrace, findings: List[Finding]) -> None:
    if not trace.is_step:
        return
    for eqn in iter_eqns(trace.jaxpr):
        name = eqn.primitive.name
        if name in HOST_TRANSFER_PRIMITIVES or "callback" in name:
            findings.append(Finding(
                "host-transfer", trace.name,
                f"host-transfer eqn '{name}' inside a per-step program — "
                f"a host round-trip per engine step"))


def _check_pool_gather(trace: ProgramTrace, findings: List[Finding]) -> None:
    """On a kernel-read engine, a per-step program must never gather a KV
    pool operand — the full-capacity ``pool[tables]`` HBM materialization is
    exactly what the block-table-walking kernel exists to remove. Pool avals
    come from the engine state, so COW block copies (not step programs) and
    table-array gathers (different avals) never false-positive."""
    if not (trace.is_step and trace.kernel_read_path and trace.pool_avals):
        return
    pools = set(trace.pool_avals)
    for eqn in iter_eqns(trace.jaxpr):
        if eqn.primitive.name != "gather" or not eqn.invars:
            continue
        aval = getattr(eqn.invars[0], "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            continue
        sig = (tuple(aval.shape), str(aval.dtype))
        if sig in pools:
            findings.append(Finding(
                "pool-gather", trace.name,
                f"gather over a KV pool operand {sig} inside a per-step "
                f"program — the use_pallas read path must stream pool "
                f"blocks through the kernel, not materialize "
                f"pool[tables] in HBM"))
            return


def _check_pool_reshard(trace: ProgramTrace, findings: List[Finding]) -> None:
    """On a sequence-sharded-pool engine, a per-step program must never
    rebuild a replicated pool. Two signatures turn the audit red:

    * an ``all_gather`` over the kv axis whose operand leads with a pool
      slab's (blocks, block_size) head — full-capacity replication on the
      wire. The legit read-side exchange (``pool_exchange``) is a masked
      ``psum`` over TABLE-sized operands (resident blocks, never capacity),
      so it can't match.
    * a ``gather`` whose operand is a full-pool aval — ``pool[tables]``
      against a replicated pool, which on a ``kv_shards > 1`` engine means
      the pool was first reassembled somewhere upstream. (The sharded jnp
      oracle reads the exchanged VIRTUAL pool, whose aval is table-shaped.)
    """
    if not (trace.is_step and trace.kv_shards > 1 and trace.pool_avals):
        return
    pools = set(trace.pool_avals)
    slab_heads = set()
    for shape, dt in pools:
        if len(shape) >= 2:
            slab_heads.add(((shape[0], shape[1]), dt))
            slab_heads.add(((shape[0] // trace.kv_shards, shape[1]), dt))
    for eqn in iter_eqns(trace.jaxpr):
        name = eqn.primitive.name
        if not eqn.invars:
            continue
        aval = getattr(eqn.invars[0], "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            continue
        if (name == "all_gather" and trace.kv_axis in _eqn_axes(eqn)
                and len(aval.shape) >= 2
                and ((aval.shape[0], aval.shape[1]),
                     str(aval.dtype)) in slab_heads):
            findings.append(Finding(
                "pool-reshard", trace.name,
                f"all_gather over kv axis {trace.kv_axis!r} with pool-slab "
                f"operand {tuple(aval.shape)} {aval.dtype} — a per-step "
                f"program re-replicating the sharded pool at full capacity; "
                f"the exchange must move only table-named blocks"))
            return
        if (name == "gather"
                and (tuple(aval.shape), str(aval.dtype)) in pools):
            findings.append(Finding(
                "pool-reshard", trace.name,
                f"gather over a full-capacity pool aval "
                f"{(tuple(aval.shape), str(aval.dtype))} in a kv-sharded "
                f"step program — pool[tables] against a replicated pool "
                f"implies the {trace.kv_shards}-way sharding was undone"))
            return


def _check_retrace(trace: ProgramTrace, findings: List[Finding]) -> None:
    if trace.retrace is None:
        return
    if str(trace.retrace()) != str(trace.jaxpr):
        findings.append(Finding(
            "retrace-mismatch", trace.name,
            "re-tracing produced a different jaxpr — the trace is "
            "value-dependent, so the compile-once contract cannot hold"))


def audit_program(trace: ProgramTrace) -> ProgramReport:
    """Run every jaxpr rule over one traced program."""
    findings: List[Finding] = []
    records = collect_collectives(trace.jaxpr, trace.axis_sizes)
    tp_records = [r for r in records if trace.tp_axis in r.axes]
    expected = bool(trace.policy is not None
                    and trace.policy.active_for(trace.n_tokens))
    if expected:
        _check_compressed_wire(trace, tp_records, findings)
        _check_compression_present(trace, tp_records, findings)
    _check_dtype_drift(trace, findings)
    _check_host_transfer(trace, findings)
    _check_pool_gather(trace, findings)
    _check_pool_reshard(trace, findings)
    _check_retrace(trace, findings)
    return ProgramReport(name=trace.name, collectives=tp_records,
                         findings=findings, compressed_expected=expected,
                         n_tokens=trace.n_tokens)


def audit_engine(engine: Any, *, label: str = "",
                 prompt_len: Optional[int] = None) -> AuditReport:
    """Trace every compiled program of ``engine`` and audit each.

    Pure tracing — nothing executes on device. ``prompt_len`` additionally
    audits the whole-prompt prefill/insert pair at that length (chunked
    engines only dispatch it via ``measure_ttft``, so it is opt-in there
    and always-on for whole-prompt engines)."""
    report = AuditReport(label=label or f"{engine.cfg.name} "
                         f"{engine.cache_spec.describe()}")
    for trace in engine.trace_programs(prompt_len=prompt_len).values():
        report.programs.append(audit_program(trace))
    return report


# --------------------------------------------------- jit-cache-key audit


def audit_static_args(paths: List[str]) -> List[Finding]:
    """Statically derive each ``jax.jit`` call site's static-arg signature
    and flag entries that are not hashable at a call site or do not name a
    parameter of the jitted function (both poison the jit cache key: the
    first raises at call time, the second retraces per call). Shares the
    resolver with lint rule SC004 so the two passes cannot disagree."""
    from repro.staticcheck.lint import lint_paths

    return [Finding("static-args", str(v.path), v.message)
            for v in lint_paths(paths, rules=("SC004",))]
