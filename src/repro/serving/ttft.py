"""Analytic TTFT model — reproduces the paper's Table 3 methodology on
hardware we cannot measure directly (CPU container; TPU v5e is the target).

The paper's profiling setup does NOT use ring all-reduce: every worker
all-gathers the *full* partial tensor from the other N-1 workers and sums
locally (§4.3). Communication per device per row-parallel reduction is
therefore (N-1) x tensor_bytes, and compression divides exactly that term.

TTFT(model, hw, B, S) =
    compute:   2 * P_active * B*S / (N * peak_flops * mfu)
  + comm:      n_reductions * (N-1) * bytes(B*S*d_model) / link_bw
  + codec:     [if compressed] n_reductions * (codec_passes * N * bytes /
               hbm_bw + fixed_launch)

Hardware constants below are public specs; ``mfu`` and effective ``link_bw``
are calibrated against the paper's *uncompressed* rows (the fit set), and
the compressed rows then validate the model (the holdout) — see
benchmarks/table3_ttft.py.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax

from repro.configs.base import ModelConfig
from repro.core.formats import MXSpec
from repro.serving.errors import OUTCOME_OK, TERMINAL_OUTCOMES

__all__ = ["Hardware", "HARDWARE", "ttft_seconds", "ttft_breakdown",
           "RequestTiming", "ServeStats", "Span"]


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops: float          # per chip, fp16/bf16 dense
    hbm_bw: float              # bytes/s per chip
    link_bw: float             # effective all-gather bytes/s per chip
    mfu: float                 # calibrated prefill MFU
    codec_fixed_s: float = 2e-4  # per-collective codec launch overhead
    codec_passes: float = 3.0    # HBM passes for quant+dequant+sum


HARDWARE: Dict[str, Hardware] = {
    # L4: PCIe Gen4 x16 host-staged collectives — low effective bw
    "L4": Hardware("L4", peak_flops=60.5e12, hbm_bw=300e9, link_bw=7.0e9, mfu=0.45),
    # A100 SXM NVLink 600 GB/s bidirectional
    "A100": Hardware("A100", peak_flops=312e12, hbm_bw=2.0e12, link_bw=180e9,
                     mfu=0.50),
    # TPU v5e: per-chip ICI ~50 GB/s/link (target platform)
    "TPUv5e": Hardware("TPUv5e", peak_flops=197e12, hbm_bw=819e9, link_bw=45e9,
                       mfu=0.55),
}


def _n_row_reductions(cfg: ModelConfig) -> int:
    """Row-parallel reductions per forward pass (attn.o + mlp/moe.down, plus
    mamba/xlstm out-proj)."""
    n = 0
    for spec in cfg.layers:
        n += 1  # core block out-proj (attn.o / mamba.out / xlstm.down)
        if spec.kind in ("attn", "mamba") and (cfg.d_ff > 0 or spec.moe):
            n += 1  # mlp or moe down
    if cfg.encoder_decoder:
        n += 2 * cfg.n_encoder_layers + cfg.n_layers  # enc layers + cross-attn
    return n


def ttft_breakdown(
    cfg: ModelConfig,
    hw: Hardware,
    tp: int,
    batch: int,
    seq: int,
    spec: MXSpec | None = None,
    *,
    bytes_per_el: float = 2.0,
    scheme: str = "gather",
) -> Dict[str, float]:
    """scheme: per-device bytes moved per reduction —
      "gather"    (N-1) x tensor        (paper's torch stack, Fig 1b)
      "ring"      2 (N-1)/N x tensor    (ring all-reduce / rs+ag: XLA on TPU)
      "two_phase" 2 (N-1)/N x tensor    on the COMPRESSED payload
                  (our beyond-paper compressed rs+ag variant)
    """
    tokens = batch * seq
    compute = 2.0 * cfg.active_param_count() * tokens / (tp * hw.peak_flops * hw.mfu)

    n_red = _n_row_reductions(cfg)
    tensor_bytes = tokens * cfg.d_model * bytes_per_el
    if spec is not None:
        wire = tensor_bytes * spec.wire_bits_per_value(cfg.d_model) / (8 * bytes_per_el)
    else:
        wire = tensor_bytes
    if scheme == "gather":
        per_red = (tp - 1) * wire
    else:  # ring / two_phase
        per_red = 2.0 * (tp - 1) / tp * wire
    comm = n_red * per_red / hw.link_bw

    codec = 0.0
    if spec is not None:
        # gather: each device dequantizes all N gathered partials;
        # two_phase: ~constant passes regardless of N
        hbm_bytes = hw.codec_passes * tensor_bytes * (tp if scheme == "gather" else 1)
        codec = n_red * (hbm_bytes / hw.hbm_bw + hw.codec_fixed_s)
    return {"compute": compute, "comm": comm, "codec": codec,
            "total": compute + comm + codec}


def ttft_seconds(cfg, hw, tp, batch, seq, spec=None, scheme: str = "gather") -> float:
    return ttft_breakdown(cfg, hw, tp, batch, seq, spec, scheme=scheme)["total"]


# ----------------------------------------------------- measured serving stats
#
# The analytic model above predicts TTFT on hardware we can't run; the
# classes below account for what the continuous-batching Engine actually
# measures per request (arrival -> admission -> first token -> finish).


@dataclasses.dataclass
class RequestTiming:
    """Wall-clock milestones and token accounting for ONE request served by
    the continuous-batching engine, relative to the run's start.

    The engine fills one of these per request at its TERMINAL outcome (also
    attached as ``Request.timing``); ``ServeStats`` aggregates them.
    ``outcome`` is one of ``TERMINAL_OUTCOMES`` (serving/errors.py):
    ``"ok"`` requests retired normally; degraded outcomes (``"rejected"`` /
    ``"timed_out"`` / ``"cancelled"``) may never have been admitted or
    sampled, so ``admitted_s`` / ``first_token_s`` are Optional and the
    derived properties return NaN when the milestone was never reached.
    Derived properties: ``ttft_s`` (arrival to first sampled token —
    queueing included), ``latency_s`` (arrival to the terminal outcome),
    ``queue_s`` (arrival to first admission).
    """

    arrival_s: float                 # request entered the system
    admitted_s: Optional[float]      # first admission (None: never admitted)
    first_token_s: Optional[float]   # first sampled token (None: none sampled)
    finished_s: float                # terminal outcome reached
    n_prompt: int                    # tokens in the ORIGINAL prompt
    n_generated: int                 # tokens sampled (== max_new_tokens
                                     # unless eos_id / a deadline / a cancel
                                     # stopped decode early)
    n_preemptions: int = 0           # evict/recompute round trips
    n_cached_prompt: int = 0         # prompt tokens served from shared
                                     # prefix-cache blocks instead of being
                                     # prefilled (summed across readmissions,
                                     # so preemption recompute counts again)
    inter_token_s: Optional[List[float]] = None  # gaps between consecutive
                                                 # sampled tokens (TPOT samples)
    outcome: str = OUTCOME_OK        # terminal outcome (TERMINAL_OUTCOMES)

    def __post_init__(self) -> None:
        if self.outcome not in TERMINAL_OUTCOMES:
            raise ValueError(
                f"unknown outcome {self.outcome!r}: expected one of "
                f"{', '.join(TERMINAL_OUTCOMES)}")

    @property
    def ttft_s(self) -> float:
        if self.first_token_s is None:
            return float("nan")
        return self.first_token_s - self.arrival_s

    @property
    def latency_s(self) -> float:
        return self.finished_s - self.arrival_s

    @property
    def queue_s(self) -> float:
        if self.admitted_s is None:
            return float("nan")
        return self.admitted_s - self.arrival_s


def _percentile(xs: List[float], p: float) -> float:
    xs = sorted(xs)
    if not xs:
        return float("nan")
    i = min(len(xs) - 1, max(0, round(p / 100 * (len(xs) - 1))))
    return xs[i]


class Span:
    """One timed span of the engine loop (``ServeStats.span``): a
    ``jax.profiler`` trace annotation on the profiler's clock, whose
    ``time.perf_counter`` length is added to the owner's ``phase_s`` /
    ``phase_n`` on exit (also on an exception) and kept in ``seconds``."""

    __slots__ = ("_stats", "_name", "_ann", "_t", "seconds")

    def __init__(self, stats: "ServeStats", name: str,
                 step_num: Optional[int]):
        self._stats, self._name = stats, name
        self._ann = (jax.profiler.TraceAnnotation(name) if step_num is None
                     else jax.profiler.StepTraceAnnotation(
                         name, step_num=step_num))
        self.seconds = 0.0

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        self._t = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t
        self._ann.__exit__(*exc)
        st = self._stats
        st.phase_s[self._name] = st.phase_s.get(self._name, 0.0) + self.seconds
        st.phase_n[self._name] = st.phase_n.get(self._name, 0) + 1


class ServeStats:
    """Aggregates ``RequestTiming`` records across one serving run.

    ``Engine.run`` resets and fills one of these per call
    (``engine.stats``); ``summary()`` reduces the records to the serving
    distributions the benchmarks report — TTFT / latency percentiles,
    pooled inter-token latency (TPOT), aggregate throughput over the
    makespan, preemption counts, and the prefix-cache accounting
    (``prefill_tokens_skipped`` / ``prefix_hit_rate``) that attributes the
    warm-TTFT win to skipped prefill work.
    """

    def __init__(self):
        self.timings: List[RequestTiming] = []
        # per-step dispatch accounting (engine-maintained): how many engine
        # steps ran, how many device programs they dispatched, and the
        # (prefill, decode) token split each step packed — the attribution
        # for the mixed-step dispatch-halving win
        self.n_steps = 0
        self.n_dispatches = 0
        self.step_tokens: List[tuple] = []  # (n_prefill, n_decode) per step
        # steps served through the COMPRESSED mixed-program gate variant
        # (per-step composition gating; dense steps are n_steps - this)
        self.n_compressed_steps = 0
        # prompt tokens processed outside budgeted steps (whole-prompt
        # prefill at admission)
        self.off_step_prefill_tokens = 0
        # tiles of the mixed step's jnp history read that hold a real row,
        # and the tiles the fixed-shape read holds, summed over steps
        self.read_tiles = 0
        self.read_tiles_held = 0
        # host seconds and counts per span name (``span``): ``engine.admit``,
        # ``engine.step`` and its disjoint phases, ``engine.idle``
        # (docs/serving.md "Observability")
        self.phase_s: Dict[str, float] = {}
        self.phase_n: Dict[str, int] = {}

    def span(self, name: str, step_num: Optional[int] = None) -> Span:
        """Context manager timing one ``name`` span into ``phase_s`` /
        ``phase_n``; with ``step_num`` it is a ``StepTraceAnnotation``, the
        key a profiler trace shares with ``n_steps``."""
        return Span(self, name, step_num)

    def record(self, t: RequestTiming) -> None:
        self.timings.append(t)

    def record_step(self, n_prefill: int, n_decode: int,
                    n_dispatches: int = 1, compressed: bool = False,
                    read_tiles: int = 0, read_tiles_held: int = 0) -> None:
        """One engine step: ``n_prefill`` prompt tokens + ``n_decode``
        decode tokens processed through ``n_dispatches`` device programs
        (1 for the unified mixed step; up to 2 — chunk + decode — for the
        split scheduler). ``compressed`` marks a step dispatched through
        the compressed gate variant of the mixed program. ``read_tiles`` of
        the ``read_tiles_held`` tiles of the mixed step's jnp history read
        hold a real row."""
        self.n_steps += 1
        self.n_dispatches += n_dispatches
        self.step_tokens.append((n_prefill, n_decode))
        if compressed:
            self.n_compressed_steps += 1
        self.read_tiles += read_tiles
        self.read_tiles_held += read_tiles_held

    def record_dispatch(self, n: int = 1, prefill_tokens: int = 0) -> None:
        """Off-step program dispatches (whole-prompt prefill + insert at
        admission, prefix-cache COW forks), with any prompt tokens they
        processed so ``prefill_tokens`` stays truthful for whole-prompt
        engines."""
        self.n_dispatches += n
        self.off_step_prefill_tokens += prefill_tokens

    def merge(self, other: "ServeStats") -> None:
        """Fold another run's records into this one — the supervisor
        aggregates per-attempt engine stats into one report this way.
        Timings are appended as-is (replayed requests re-record under their
        final attempt; the supervisor drops superseded records first)."""
        self.timings.extend(other.timings)
        self.n_steps += other.n_steps
        self.n_dispatches += other.n_dispatches
        self.step_tokens.extend(other.step_tokens)
        self.n_compressed_steps += other.n_compressed_steps
        self.off_step_prefill_tokens += other.off_step_prefill_tokens
        self.read_tiles += other.read_tiles
        self.read_tiles_held += other.read_tiles_held
        for name, sec in other.phase_s.items():
            self.phase_s[name] = self.phase_s.get(name, 0.0) + sec
        for name, n in other.phase_n.items():
            self.phase_n[name] = self.phase_n.get(name, 0) + n

    def summary(self) -> Dict[str, float]:
        """Aggregate the run. Keys (seconds unless noted):

        - ``n_requests`` / ``n_generated`` / ``makespan_s`` /
          ``tokens_per_s`` — run totals (throughput over the makespan).
        - ``ttft_{p50,p90,mean}_s`` and ``latency_{p50,p90}_s`` — arrival-
          anchored per-request distributions (queueing included).
        - ``tpot_{p50,p95}_s`` over ``n_inter_token_samples`` — gaps
          between consecutive sampled tokens pooled across requests: the
          decode-side metric head-of-line blocking inflates (chunked
          prefill bounds the stall to one chunk). Defined (0.0) even when
          no request emits a second token — single-token traffic has no
          gaps, and the summary must stay NaN-free.
        - ``n_steps`` / ``n_dispatches`` / ``tokens_per_step_mean`` /
          ``prefill_tokens`` / ``decode_tokens`` — per-step dispatch
          accounting: engine steps, device programs dispatched, and the
          packed token mix (the mixed token-budget step dispatches ONE
          program per step where the split scheduler paid two).
        - ``n_compressed_steps`` — steps dispatched through the compressed
          mixed-program gate variant (per-step composition gating).
        - ``read_tile_share`` — tiles of the mixed step's jnp history read
          that hold a real row, over the tiles its fixed shape holds (steps
          x ``mixed_read_tiles``); 0.0 where no step took that read.
        - ``n_preemptions`` — evict-and-recompute round trips.
        - ``prefill_tokens_skipped`` — prompt tokens served from shared
          prefix-cache blocks instead of recomputed; ``prefix_hit_rate``
          normalizes by original prompt tokens (can exceed 1.0 when
          preempted requests re-skip on readmission).
        - ``n_{ok,rejected,timed_out,cancelled}`` — terminal outcome
          counts (sum to ``n_requests``); ``goodput_tokens_per_s`` counts
          only tokens from ``ok`` requests over the makespan — tokens spent
          on requests that later timed out or were cancelled are throughput
          but not goodput. TTFT percentiles cover only requests that
          produced a first token; latency percentiles cover every request
          (arrival to terminal outcome).
        """
        ts = self.timings
        if not ts:
            return {"n_requests": 0}
        ttfts = [t.ttft_s for t in ts if t.first_token_s is not None]
        lats = [t.latency_s for t in ts]
        # inter-token latency (TPOT) pooled across requests: the decode-side
        # metric that head-of-line blocking inflates (a whole-prompt prefill
        # stalls every running decode for its full duration; chunked prefill
        # bounds the stall to one chunk)
        gaps = [g for t in ts for g in (t.inter_token_s or [])]
        generated = sum(t.n_generated for t in ts)
        makespan = max(t.finished_s for t in ts) - min(t.arrival_s for t in ts)
        prompt_tokens = sum(t.n_prompt for t in ts)
        cached = sum(t.n_cached_prompt for t in ts)
        step_total = sum(p + d for p, d in self.step_tokens)
        outcomes = {o: sum(1 for t in ts if t.outcome == o)
                    for o in TERMINAL_OUTCOMES}
        good = sum(t.n_generated for t in ts if t.outcome == OUTCOME_OK)
        return {
            "n_requests": len(ts),
            # all-degraded runs have no first tokens; stay NaN-free
            "ttft_p50_s": _percentile(ttfts, 50) if ttfts else 0.0,
            "ttft_p90_s": _percentile(ttfts, 90) if ttfts else 0.0,
            "ttft_mean_s": sum(ttfts) / len(ttfts) if ttfts else 0.0,
            "latency_p50_s": _percentile(lats, 50),
            "latency_p90_s": _percentile(lats, 90),
            # no-gap traffic (every request emits a single token) has no
            # TPOT samples; report 0.0 rather than a NaN percentile
            "tpot_p50_s": _percentile(gaps, 50) if gaps else 0.0,
            "tpot_p95_s": _percentile(gaps, 95) if gaps else 0.0,
            "n_inter_token_samples": len(gaps),
            "n_steps": self.n_steps,
            "n_dispatches": self.n_dispatches,
            "n_compressed_steps": self.n_compressed_steps,
            "read_tile_share": (self.read_tiles / self.read_tiles_held
                                if self.read_tiles_held else 0.0),
            "tokens_per_step_mean": (step_total / self.n_steps
                                     if self.n_steps else 0.0),
            "prefill_tokens": (sum(p for p, _ in self.step_tokens)
                               + self.off_step_prefill_tokens),
            "decode_tokens": sum(d for _, d in self.step_tokens),
            "n_generated": generated,
            "makespan_s": makespan,
            "tokens_per_s": generated / makespan if makespan > 0 else float("nan"),
            "n_preemptions": sum(t.n_preemptions for t in ts),
            "prefill_tokens_skipped": cached,
            "prefix_hit_rate": (cached / prompt_tokens if prompt_tokens
                                else 0.0),
            "n_ok": outcomes[OUTCOME_OK],
            "n_rejected": outcomes["rejected"],
            "n_timed_out": outcomes["timed_out"],
            "n_cancelled": outcomes["cancelled"],
            "goodput_tokens_per_s": (good / makespan if makespan > 0
                                     else float("nan")),
        }
