"""One general traffic generator, driven by a mix file in ``traffic/``.

A mix file is JSON::

    {"arrival": "poisson", "rate_per_s": 3.0, "drain_s": 10,
     "prompt_len": {"dist": "lognormal", "median": 128, "sigma": 1.0,
                    "min": 16, "max": 768},
     "output_len": {"dist": "uniform", "min": 384, "max": 768}}

``arrival`` is ``poisson`` (open loop: ``rate_per_s`` over the window, the
run drains for at most ``drain_s`` after it) or ``backlog`` (``count``
requests present at t=0, no drain). Lengths are ``lognormal`` (by median
and sigma of the log) or ``uniform`` (inclusive), clipped to ``[min, max]``.

Every seed gets the same lengths and arrival times, in the same order, drawn
from the mix's own fixed stream (``size_seed``, default 0); the run's seed
draws only the token ids (and, in the harness, the weights). So two seeds
do the same work on the same schedule, and the spread between seeds is the
system's, not the generator's: with the order permuted per seed, which long
prompts and outputs fell inside the window moved the output rate of the chat
cell by about 10% between seeds (measured on one v5e chip). (Copied from ``benchmarks/serve_throughput.py:build_requests``
— seeded Poisson arrivals — and extended with length distributions,
clipping, backlog arrivals and the fixed multiset.)
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import List

import numpy as np

__all__ = ["Mix", "Planned", "load_mix", "plan", "seed_words", "window_count"]


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request of the schedule, before it becomes an engine Request."""

    arrival_s: float
    prompt: np.ndarray        # int32 token ids
    max_new_tokens: int


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    arrival: str              # "poisson" | "backlog"
    prompt_len: dict
    output_len: dict
    rate_per_s: float = 0.0
    count: int = 0
    drain_s: float = 0.0
    size_seed: int = 0

    @property
    def open_loop(self) -> bool:
        return self.arrival == "poisson"


def load_mix(path) -> Mix:
    path = Path(path)
    raw = json.loads(path.read_text())
    mix = Mix(name=path.stem, **{k: v for k, v in raw.items()
                                 if not k.startswith("_")})
    if mix.arrival not in ("poisson", "backlog"):
        raise ValueError(f"{path}: arrival must be poisson or backlog, "
                         f"got {mix.arrival!r}")
    if mix.open_loop and not mix.rate_per_s > 0:
        raise ValueError(f"{path}: a poisson mix needs rate_per_s > 0")
    if not mix.open_loop and mix.count <= 0:
        raise ValueError(f"{path}: a backlog mix needs count > 0")
    for d in (mix.prompt_len, mix.output_len):
        if d["dist"] not in ("lognormal", "uniform") or d["min"] > d["max"]:
            raise ValueError(f"{path}: bad length distribution {d}")
    return mix


def _lengths(d: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if d["dist"] == "lognormal":
        x = np.exp(rng.normal(math.log(d["median"]), d["sigma"], n))
        x = np.rint(x)
    else:
        x = rng.integers(d["min"], d["max"] + 1, n)
    return np.clip(x, d["min"], d["max"]).astype(np.int64)


def window_count(mix: Mix, seconds: float) -> int:
    """Requests one run sends: the rate times the window (open loop), or the
    backlog's count."""
    if mix.open_loop:
        return max(1, int(round(mix.rate_per_s * seconds)))
    return mix.count


def seed_words(seed: int) -> List[int]:
    """Any whole number (negative or past 64 bits) as SeedSequence entropy."""
    seed = int(seed)
    words = [1 if seed < 0 else 0]
    seed = abs(seed)
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


def plan(mix: Mix, seconds: float, seed: int, vocab: int) -> List[Planned]:
    """The run's schedule, sorted by arrival."""
    n = window_count(mix, seconds)
    fixed = np.random.default_rng(mix.size_seed)
    plens = _lengths(mix.prompt_len, n, fixed)
    olens = _lengths(mix.output_len, n, fixed)
    if mix.open_loop:
        # n exponential gaps, rescaled so the n-th arrival lands on the
        # window's last 1/n: arrivals fill the window at the stated rate
        gaps = fixed.exponential(1.0, n)
        gaps *= seconds * (n - 0.5) / n / gaps.sum()
    else:
        gaps = np.zeros(n)
    rng = np.random.default_rng(np.random.SeedSequence(seed_words(seed)))
    arrivals = np.cumsum(gaps) - (gaps[0] if mix.open_loop else 0.0)
    return [Planned(arrival_s=float(arrivals[i]),
                    prompt=rng.integers(0, vocab, int(plens[i]),
                                        dtype=np.int64).astype(np.int32),
                    max_new_tokens=int(olens[i]))
            for i in range(n)]
