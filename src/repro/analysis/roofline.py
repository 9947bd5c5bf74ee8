"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds (TPU v5e constants):
    compute    = HLO_FLOPs / peak_FLOP/s           (per-chip SPMD module)
    memory     = HLO_bytes / HBM_bw
    collective = collective_bytes / ICI_bw

collective_bytes is not in cost_analysis: we parse the compiled HLO and sum
operand/output sizes of every collective op, weighted by how many times the
payload crosses a link per device (all-reduce counts 2x: reduce+broadcast
phases; gather/scatter/all-to-all count 1x their moved payload).
"""
from __future__ import annotations

import re
from typing import Dict

from repro.analysis import hw

__all__ = ["parse_collective_bytes", "roofline_terms", "analyze_compiled",
           "model_flops"]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = {
    "all-gather": 1.0,
    "all-reduce": 2.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
    "ragged-all-to-all": 1.0,
}
_OP_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:\w+\[[\d,]*\][^\s]*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"ragged-all-to-all)(?:-start|-done)?\("
)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collective_bytes(hlo_text: str) -> Dict[str, float]:
    out: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    seen_done = set()
    for m in _OP_RE.finditer(hlo_text):
        shape_str, op = m.group(1), m.group(2)
        # avoid double counting async -start/-done pairs: -done repeats shape
        if m.group(0).split("(")[0].endswith("-done("):
            continue
        if "-done(" in m.group(0):
            continue
        out[op] += _shape_bytes(shape_str) * _COLLECTIVES[op]
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def roofline_terms(flops: float, bytes_accessed: float,
                   collective_bytes: float) -> Dict[str, float]:
    compute = flops / hw.PEAK_FLOPS_BF16
    memory = bytes_accessed / hw.HBM_BW
    collective = collective_bytes / hw.ICI_BW
    terms = {"compute_s": compute, "memory_s": memory, "collective_s": collective}
    terms["dominant"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k]
    )
    terms["bound_s"] = max(compute, memory, collective)
    return terms


def model_flops(cfg, tokens: int, *, train: bool) -> float:
    """6ND (train) / 2ND (inference) with N = active params."""
    n = cfg.active_param_count()
    return (6.0 if train else 2.0) * n * tokens


def analyze_compiled(compiled, *, n_chips: int, cfg=None, tokens: int = 0,
                     train: bool = False) -> Dict:
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)
    terms = roofline_terms(flops, bytes_accessed, coll["total"])
    mem = compiled.memory_analysis()
    result = {
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_accessed,
        "collective_bytes_per_chip": coll["total"],
        "collectives": {k: v for k, v in coll.items() if k != "total"},
        **terms,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_est_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes,
        },
        "n_chips": n_chips,
    }
    if cfg is not None and tokens:
        mf = model_flops(cfg, tokens, train=train)
        result["model_flops_total"] = mf
        result["model_flops_per_chip"] = mf / n_chips
        denom = flops * n_chips
        result["useful_flops_ratio"] = mf / denom if denom else 0.0
    return result
