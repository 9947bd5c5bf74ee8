"""Continuous-batching serving engine over a paged KV cache.

This is the deployment surface the paper profiles: prefill is where the
compressed TP collectives pay off; decode is policy-gated to uncompressed
(paper §5.2/A100 finding: codec overhead loses when payloads are small).
In the unified mixed step that gate is PER STEP: the engine compiles one
mixed program per gate variant (compressed / dense — same shapes, different
collectives) and dispatches the variant matching each step's REAL token
composition via ``CompressionPolicy.active_for_step`` (prefill-dominated
steps take the compressed wire, decode-dominated steps stay dense).
Architecture, invariants, and the compression gating between prefill and
decode are documented in DESIGN.md §Gating.

Prefill is CHUNKED by default (Sarathi-style token-budget scheduling), and
for pure-attention text archs the whole step is ONE program: every engine
step flattens up to ``token_budget`` tokens — several PREFILLING slots'
prompt chunks plus one token per DECODING slot — into a single mixed batch
and dispatches one ``Model.mixed_step`` program (compiled exactly once;
shapes depend only on the budget and slot count). That halves program
dispatches per step vs the split chunk-then-decode pair — on a TP mesh,
half the collective launches per step — while long prompts still stream in
chunk-by-chunk without stalling running decodes (head-of-line blocking).
``token_budget=0`` keeps the split scheduler (one chunk program, then the
batched decode); architectures the flattened program can't serve
(recurrent layers, vision prefix, encoder-decoder) fall back to the
whole-prompt prefill/insert pair plus batched decode.

With ``prefix_cache=True`` the engine additionally shares KV blocks across
requests with a common prompt prefix (docs/serving.md): full prompt blocks
are published in a hash-chain index as their chunks land, admission maps
matching blocks into the new slot's table by reference (refcounted), and
chunked prefill resumes at the first non-cached token — warm requests skip
the shared prefill work and still decode exactly what a cold engine
decodes.

Shape-stability contract: the batched decode step always runs over all
``max_slots`` slots and the chunk program's shapes are independent of prompt
length, so requests joining and leaving mid-flight never trigger
recompilation — ``decode_cache_size()`` and ``prefill_cache_size()`` both
stay at one compiled program per gate variant for a whole run (one for a
dense engine, two — compressed + dense — under an active policy;
prefix-cache hits only edit the host-side block table, never program
shapes).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import math
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import KVCacheSpec, MXSpec
from repro.core.mx import MXCompressed
from repro.core.policy import NO_COMPRESSION
from repro.core.tp import (
    TPContext, constrain, pool_block_copy, pool_block_fill, pool_block_write,
)
from repro.launch.sharding import param_shardings
from repro.models.attention import (
    constrain_wire_pool, quantize_kv_pages, read_tiles_held, read_tiles_used,
)
from repro.models.model import Model
from repro.serving.errors import (
    OUTCOME_CANCELLED, OUTCOME_OK, OUTCOME_REJECTED, OUTCOME_TIMED_OUT,
    EngineDead, InvalidRequest, PoolExhausted, SlotExhausted, StepStuck,
    WireCorruption,
)
from repro.serving.faults import FaultPlan
from repro.serving.kv_cache import (
    BlockAllocator, PrefixIndex, build_mixed_batch, check_cache_spec,
    init_paged_state, paged_cache_bytes,
)
from repro.serving.ttft import RequestTiming, ServeStats

__all__ = ["Request", "Engine"]


@dataclasses.dataclass
class Request:
    prompt: np.ndarray            # int32 token ids (validated non-empty)
    max_new_tokens: int = 16
    temperature: float = 0.0
    arrival_s: float = 0.0        # offset from run() start (staggered traffic)
    eos_id: Optional[int] = None  # stop early on this token
    # per-request deadlines, measured from arrival (None = engine default;
    # the engine's own None = no deadline). Expiry is a terminal OUTCOME
    # (timing.outcome == "timed_out"), never an exception: the request
    # leaves with whatever tokens it generated and its blocks are freed.
    deadline_ttft_s: Optional[float] = None   # first token must land by this
    deadline_s: Optional[float] = None        # last token must land by this
    cancelled: bool = False       # set via cancel(); swept at the next step
    # filled by the engine:
    output: Optional[np.ndarray] = None
    first_logits: Optional[np.ndarray] = None  # (V,) float32, with
                                               # Engine(keep_first_logits=)
    ttft_s: Optional[float] = None
    latency_s: Optional[float] = None
    timing: Optional[RequestTiming] = None

    def __post_init__(self) -> None:
        if np.asarray(self.prompt).size == 0:
            raise InvalidRequest(
                "request prompt is empty — a request needs at least one "
                "prompt token")
        if self.max_new_tokens <= 0:
            raise InvalidRequest(
                f"max_new_tokens must be >= 1 (a request must generate at "
                f"least one token), got {self.max_new_tokens}")
        for name in ("deadline_ttft_s", "deadline_s"):
            d = getattr(self, name)
            if d is not None and d <= 0:
                raise InvalidRequest(
                    f"{name} must be > 0 seconds (measured from arrival), "
                    f"got {d}")

    def cancel(self) -> None:
        """Mark for cancellation: the engine sweeps the flag at its next
        step boundary, releases any KV blocks the request holds (mid-decode
        included), and records outcome ``"cancelled"`` with whatever tokens
        were already generated. Safe to call from another thread — the
        flag is only ever flipped one way."""
        self.cancelled = True

    @property
    def outcome(self) -> Optional[str]:
        """Terminal outcome (``TERMINAL_OUTCOMES``), None while in flight."""
        return self.timing.outcome if self.timing is not None else None


@dataclasses.dataclass
class _Work:
    """Scheduler-internal request state (survives preemptions)."""

    req: Request
    prompt: np.ndarray            # effective prompt: original + generated on
                                  # readmission after a preemption (recompute)
    extra: Dict[str, jnp.ndarray]  # per-request model extras (1, ...) slices
    arrival: float
    tokens: List[int] = dataclasses.field(default_factory=list)
    blocks: List[int] = dataclasses.field(default_factory=list)
    admitted_t: Optional[float] = None
    first_token_t: Optional[float] = None
    preemptions: int = 0
    # chunked-prefill state: a slot is PREFILLING while pos < len(prompt)
    # (its prompt is streaming into the pools chunk by chunk) and DECODING
    # after its first token is sampled
    prefilling: bool = False
    pos: int = 0                  # prompt tokens already written to the pools
    token_times: List[float] = dataclasses.field(default_factory=list)
    # prefix-cache state: rolling block hashes of the effective prompt
    # (recomputed per admission — preemption folds generated tokens in) and
    # the running count of prompt tokens served from shared blocks
    hashes: Optional[List[int]] = None
    cached_tokens: int = 0

    @property
    def done(self) -> bool:
        if len(self.tokens) >= self.req.max_new_tokens:
            return True
        return (self.req.eos_id is not None and self.tokens
                and self.tokens[-1] == self.req.eos_id)


class Engine:
    """Continuous-batching serving engine over a paged KV cache.

    Scheduling: FIFO admission by arrival time into ``max_slots`` decode
    slots, chunked prefill interleaved with batched decode (one chunk + one
    decode per engine step), LIFO preemption (evict-and-recompute) under
    block pressure. See DESIGN.md for invariants and docs/serving.md for
    the request-lifecycle walkthrough.

    Key constructor knobs (all host-side; none change compiled shapes):

    - ``max_slots`` / ``max_len`` — decode batch width and per-request
      position capacity (``max_len`` rounds up to whole blocks).
    - ``block_size`` / ``n_blocks`` — KV paging granularity and pool size;
      ``n_blocks`` defaults to full provisioning (every slot can hold
      ``max_len``), smaller values exercise eviction.
    - ``cache_spec`` — pool storage: dense ``cache_dtype`` (default,
      bit-identical to the pre-quantization engine) or an MX wire format
      (``"fp4_e2m1"``; ~3.76x resident blocks per byte).
    - ``prefill_chunk`` — prompt tokens per PREFILLING slot per engine
      step; defaults to ``2*block_size`` for pure-attention archs and ``0``
      (whole-prompt fallback) otherwise.
    - ``token_budget`` — flattened tokens per engine step for the unified
      mixed-batch program (DESIGN.md §Mixed step); defaults to
      ``prefill_chunk + max_slots`` on chunk-capable archs (every DECODING
      slot's token plus one full chunk — also the enforced floor, so full
      split-schedule chunks always fit and packing never truncates one).
      ``0`` selects the split scheduler (chunk program, then batched
      decode — two dispatches per step).
    - ``prefix_cache`` — automatic prefix caching over refcounted blocks
      (requires chunked prefill); ``False`` (default) is bit-identical to
      the engine without the feature.
    - ``persistent_cache`` — keep the paged pools, allocator, and prefix
      index warm across ``run()`` calls (requires ``prefix_cache``), so a
      second run of the same system prompt skips its prefill.
    - ``compress_decode`` — lift the paper-§5.2 gating and run decode
      collectives compressed too (default off: decode payloads are small).
      The mixed step always runs under the prefill context: its collective
      payloads are budget-sized (chunk-scale), not one-token.
    - robustness knobs (docs/serving.md §Failure modes & recovery):
      ``max_queue`` (admission bound — overflow arrivals leave REJECTED),
      ``deadline_ttft_s`` / ``deadline_s`` (engine-default deadlines;
      expiry frees blocks mid-decode and records TIMED_OUT),
      ``fault_plan`` (deterministic fault injection, serving/faults.py),
      ``step_timeout_s`` / ``stall_limit`` (step watchdog + stall guard,
      both raising ``StepStuck``), and ``max_preempts_per_step`` /
      ``thrash_window`` / ``thrash_limit`` (eviction-storm guard: bounded
      preemptions per step, with sustained thrash degrading the engine to
      one chunk per step and no admissions until a retire).
    - ``check_finite`` — check every sampled row's logits for non-finite
      values (``WireCorruption`` on the first), at one small device->host
      read per step; always on under a fault plan that corrupts pools.
    - ``keep_first_logits`` — copy each request's first-token logits row
      (the prefill's last position) to ``Request.first_logits``, for
      comparing read paths or formats on the same prompts.

    Parameters are placed on the context's mesh by ``Model.param_specs``
    (a no-op for parameters already placed so, and without a mesh).

    ``run(requests)`` serves a list of ``Request``s and fills their
    ``output``/``ttft_s``/``latency_s``/``timing`` (``timing.outcome`` is
    the terminal outcome); per-run aggregates land in ``self.stats``
    (``ServeStats``). A run aborted by ``EngineDead`` / ``StepStuck`` /
    ``WireCorruption`` is resumable: ``recover()`` (or the
    ``EngineSupervisor``) restores a runnable engine and unfinished
    requests replay from host-side state.
    """

    PREFILL_FN_CACHE_MAX = 8  # LRU bound on whole-prompt prefill programs

    def __init__(self, model: Model, params, ctx: TPContext, *,
                 max_len: int, batch_size: Optional[int] = None,
                 max_slots: Optional[int] = None, block_size: int = 16,
                 n_blocks: Optional[int] = None, cache_dtype=jnp.bfloat16,
                 cache_spec: KVCacheSpec | MXSpec | str | None = None,
                 compress_decode: bool = False,
                 prefill_chunk: Optional[int] = None,
                 token_budget: Optional[int] = None,
                 prefix_cache: bool = False,
                 persistent_cache: bool = False,
                 donate_cache: bool = True,
                 max_queue: Optional[int] = None,
                 deadline_ttft_s: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 fault_plan: Optional[FaultPlan] = None,
                 step_timeout_s: Optional[float] = None,
                 stall_limit: int = 256,
                 max_preempts_per_step: Optional[int] = None,
                 thrash_window: int = 8,
                 thrash_limit: Optional[int] = None,
                 check_finite: bool = False,
                 keep_first_logits: bool = False):
        self.model = model
        self.cfg = model.cfg
        self.ctx = ctx
        shardings = param_shardings(model, ctx)
        self.params = (params if shardings is None
                       else jax.device_put(params, shardings))
        if max_slots is not None and max_slots <= 0:
            raise SlotExhausted(
                f"max_slots must be >= 1 (every request needs a decode "
                f"slot), got {max_slots}")
        self.n_slots = max_slots or batch_size or 4
        self.batch_size = self.n_slots  # back-compat alias
        self.max_len = max_len
        self.block_size = block_size
        self.max_blocks = -(-max_len // block_size)
        # full provisioning by default (+1 for the reserved null block);
        # pass a smaller n_blocks to exercise eviction under memory pressure
        self.n_blocks = n_blocks or (self.n_slots * self.max_blocks + 1)
        # sequence-sharded pools (DESIGN.md §Sequence-sharded pools): when
        # the context carries a kv axis, each device on it owns a contiguous
        # capacity/kv_shards slice of the pool's block dimension, so capacity
        # must divide evenly — round UP (never shrink what the caller sized)
        self.kv_shards = ctx.kv_shards
        if self.n_blocks % self.kv_shards:
            self.n_blocks += self.kv_shards - self.n_blocks % self.kv_shards
        self.cache_dtype = cache_dtype
        # KV pool storage format: dense cache_dtype (default, bit-identical
        # to the pre-quantization engine) or MX wire format (DESIGN.md
        # §Quantized cache). Accepts a KVCacheSpec or a CLI string.
        self.cache_spec = check_cache_spec(self.cfg, cache_spec)
        self.stats = ServeStats()

        # ---- robustness knobs (docs/serving.md §Failure modes & recovery).
        # All host-side: none change compiled shapes; degradation packs
        # fewer REAL tokens into the same fixed-shape step program.
        # bounded admission: arrived-but-never-admitted requests beyond this
        # leave as REJECTED (preempted requeues are exempt — they were
        # already accepted); None = unbounded
        if max_queue is not None and max_queue < 0:
            raise ValueError("max_queue must be >= 0 (None = unbounded)")
        self.max_queue = max_queue
        # engine-default deadlines; per-request fields override
        self.deadline_ttft_s = deadline_ttft_s
        self.deadline_s = deadline_s
        # deterministic fault injection (serving/faults.py)
        self.fault_plan = fault_plan
        # step watchdog: a single step's wall time past this raises
        # StepStuck (checked post-hoc at the step boundary — stands in for
        # the async watchdog thread a live server would run); the stall
        # guard raises it when the scheduler makes zero token progress for
        # stall_limit consecutive steps with requests in flight (0 = off).
        # Fault-held pool pressure is exempt: it resolves on schedule.
        self.step_timeout_s = step_timeout_s
        self.stall_limit = int(stall_limit)
        # eviction-storm guard: chunk allocation stops choosing new victims
        # once a step has preempted this many slots (chunks defer in place;
        # decode growth still preempts for correctness but counts), and
        # when a rolling window of steps preempts more than thrash_limit
        # the engine DEGRADES — no new admissions, one prefill chunk per
        # step — until a request retires and clears it.
        self.max_preempts_per_step = (max_preempts_per_step
                                      if max_preempts_per_step is not None
                                      else 2 * self.n_slots)
        self.thrash_window = int(thrash_window)
        self.thrash_limit = (thrash_limit if thrash_limit is not None
                             else 4 * self.n_slots)
        # non-finite logits watch (WireCorruption detection) costs one tiny
        # program + a device->host read per step: on only under a fault plan
        # that can corrupt pool bytes
        self._nan_watch = check_finite or (fault_plan is not None and any(
            f.kind == "corrupt" for f in fault_plan.faults))
        self.keep_first_logits = bool(keep_first_logits)

        # right-padding to a bucket is only sound when every layer is
        # attention (causal masking hides trailing pads); recurrent layers
        # fold pads into their state, so those archs prefill at exact length
        self._pad_ok = all(s.kind == "attn" for s in self.cfg.layers)
        self._n_prefix = self.cfg.n_patches if self.cfg.frontend == "vision" else 0

        # chunked prefill (DESIGN.md §Chunked prefill) needs a pure-attention
        # decoder with no prefix tokens or encoder state threading through
        # the chunk program; everything else takes the whole-prompt path
        chunk_ok = (self._pad_ok and self._n_prefix == 0
                    and not self.cfg.encoder_decoder)
        if prefill_chunk is None:
            prefill_chunk = 2 * block_size if chunk_ok else 0
        elif prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0 (0 = whole-prompt)")
        elif prefill_chunk and not chunk_ok:
            raise ValueError(
                "prefill_chunk requires a pure-attention text decoder "
                "(recurrent/vision/encoder-decoder archs use whole-prompt "
                "prefill; pass prefill_chunk=0 or leave it unset)")
        self.prefill_chunk = int(prefill_chunk)

        # unified mixed-batch step (DESIGN.md §Mixed step): one token-budget
        # program per step packing several prefill chunks + the decode batch.
        # Rides on the chunked scheduler, so it has the same arch gate; 0
        # selects the split chunk-then-decode path (two dispatches per step).
        if token_budget is None:
            token_budget = (self.prefill_chunk + self.n_slots
                            if self.prefill_chunk else 0)
        elif token_budget < 0:
            raise ValueError("token_budget must be >= 0 (0 = split steps)")
        elif token_budget and not self.prefill_chunk:
            raise ValueError(
                "token_budget (the unified mixed-batch step) rides on "
                "chunked prefill; this engine is whole-prompt "
                "(prefill_chunk=0 or a non-chunkable architecture)")
        elif token_budget and token_budget < self.n_slots + self.prefill_chunk:
            # one decode token per slot (decode is never dropped for
            # prefill work) PLUS one full chunk: packing only ever places
            # FULL split-schedule chunks (never budget-truncated ones, so
            # chunk boundaries — and therefore which tokens attend each
            # other at compute vs pool precision, and the bytes published
            # to the prefix index — are identical to the split scheduler's
            # regardless of packing timing), and this floor guarantees the
            # earliest-arrival prefilling slot always fits its chunk
            raise ValueError(
                f"token_budget ({token_budget}) must cover one decode token "
                f"per slot plus one full prefill chunk "
                f"(max_slots={self.n_slots} + prefill_chunk="
                f"{self.prefill_chunk}); shrink prefill_chunk for a "
                f"smaller step")
        self.token_budget = int(token_budget)
        # tiles the mixed step's history read holds each step
        self._read_tiles_held = read_tiles_held(
            self.token_budget, self.n_slots, self.cache_spec)

        # automatic prefix caching (DESIGN.md §Prefix caching): full prompt
        # blocks are published in a hash-chain index and mapped by reference
        # into later requests' block tables. Matching rides on the chunked
        # scheduler (prefill resumes at the first non-cached token), so it
        # requires a chunked engine; matches are truncated to prefill_chunk
        # multiples, which keeps warm suffix computation chunk-aligned with
        # the original writer's and therefore bit-identical.
        self.prefix_cache = bool(prefix_cache)
        if self.prefix_cache and not self.prefill_chunk:
            raise ValueError(
                "prefix_cache rides on chunked prefill (matches resume at "
                "the first non-cached token); this engine is whole-prompt "
                "(prefill_chunk=0 or a non-chunkable architecture)")
        # cross-run prefix persistence: keep pools + allocator + index warm
        # across run() calls so a later run's matching prompts skip prefill.
        # Useless without the index (warm pool bytes are unreachable), so it
        # requires the prefix cache.
        self.persistent_cache = bool(persistent_cache)
        if self.persistent_cache and not self.prefix_cache:
            raise ValueError(
                "persistent_cache keeps the prefix index warm across runs; "
                "it requires prefix_cache=True (warm pool bytes are only "
                "reachable through the index)")
        # pools store the exact values prefill computed only when they are
        # dense at the model's compute dtype; quantized or down-cast pools
        # are lossy, so a mid-chunk resume would attend pool-precision
        # history where the cold run attended compute-precision values —
        # _match_prefix gates the COW fast path on this (lossy pools resume
        # at a chunk-aligned boundary instead, which is exact)
        self._exact_pools = (not self.cache_spec.quantized and
                             jnp.dtype(self.cache_dtype) ==
                             jnp.dtype(self.cfg.dtype))

        # paper §5.2 gating: compression pays on prefill's large payloads;
        # decode moves one token per slot, so it defaults to plain psum
        self.ctx_decode = ctx if compress_decode else dataclasses.replace(
            ctx, policy=NO_COMPRESSION)
        # per-step gate for the unified mixed program: active_for_step runs
        # on the batch's REAL (valid) token counts, not the padded budget.
        # compress_decode lifts the prefill-fraction requirement so decode-
        # dominated mixed steps compress too (its split-path meaning).
        self._gate_policy = (dataclasses.replace(ctx.policy,
                                                 min_prefill_fraction=0.0)
                             if compress_decode else ctx.policy)
        self.gate_counts = {"compressed": 0, "dense": 0}

        donate = (2,) if donate_cache else ()
        self._insert_donate = (0,) if donate_cache else ()
        cache_spec = self.cache_spec  # closed over statically by the jit
        self._decode = jax.jit(
            lambda p, toks, state, tables, lengths: model.decode_step_paged(
                self.ctx_decode, p, toks, state, tables, lengths,
                cache_spec=cache_spec),
            donate_argnums=donate)
        self._sample = jax.jit(self._sample_impl)
        # pin the freshly-initialized pools to the canonical sharding every
        # producer (chunk append, prefill-insert, decode write) constrains
        # to, so the FIRST consumer of a reset state sees the same input
        # layout as every later call and never compiles a second variant
        a = ctx.axis if ctx.tp else None
        kv0 = ctx.kv_axis if ctx.kv_sharded else None
        pin1 = lambda p: (constrain_wire_pool(ctx, p)
                          if isinstance(p, MXCompressed)
                          else constrain(ctx, p, kv0, None, a))
        self._pin_state = jax.jit(lambda state: {
            **state,
            "pools_k": [pin1(p) for p in state["pools_k"]],
            "pools_v": [pin1(p) for p in state["pools_v"]],
        }, donate_argnums=(0,) if donate_cache else ())
        # whole-prompt prefill programs, one per LENGTH BUCKET. With chunking
        # on this cache sits idle (measure_ttft aside); without it, it is
        # LRU-bounded so mixed prompt lengths can't grow compiled programs
        # without limit (hybrid archs compile per exact length).
        self._prefill_fns: "collections.OrderedDict[int, tuple]" = \
            collections.OrderedDict()
        self._evicted_prefill_compiles = 0  # compiles lost to LRU drops
        # ONE chunk program for every prompt length (the per-bucket compile
        # storm collapses to a single compilation). Only the split scheduler
        # dispatches it; the mixed step subsumes it below.
        self._chunk_fn = None
        if self.prefill_chunk and not self.token_budget:
            self._chunk_fn = jax.jit(
                lambda p, toks, state, row, start, n_valid:
                    model.prefill_chunk(ctx, p, toks, state, row, start,
                                        n_valid, cache_spec=cache_spec),
                donate_argnums=(2,) if donate_cache else ())
        # the unified mixed-batch programs: the whole step's work (packed
        # prefill chunks + the decode batch) in ONE dispatch, compiled once
        # PER GATE VARIANT. Under an active policy the engine holds a
        # compressed variant (built under ctx — budget-sized payloads, the
        # large-payload regime where the paper's codec pays) and a dense
        # variant (ctx.without_compression() — identical shapes, plain
        # psum); _step_mixed picks the variant from the step's REAL token
        # composition (CompressionPolicy.active_for_step). No shape changes,
        # no recompiles: shapes stay fixed by token_budget/n_slots/
        # max_blocks. A dense policy keeps the single dense variant.
        self._gate_ctxs: Dict[bool, TPContext] = {}
        self._mixed_fns: Dict[bool, Any] = {}
        if self.token_budget:
            self._gate_ctxs[False] = ctx.without_compression()
            if ctx.policy.enabled and ctx.policy.compress_tp_reduce:
                self._gate_ctxs[True] = ctx
            for gate, gctx in self._gate_ctxs.items():
                self._mixed_fns[gate] = jax.jit(
                    lambda p, toks, state, slot_ids, positions, valid,
                           is_dec, starts, tables, sample_idx, _ctx=gctx:
                        model.mixed_step(_ctx, p, toks, state, slot_ids,
                                         positions, valid, is_dec, starts,
                                         tables, sample_idx,
                                         cache_spec=cache_spec),
                    donate_argnums=(2,) if donate_cache else ())
        # copy-on-write block fork (prefix caching): duplicate one block's
        # bytes in every attention layer's K/V pool so a slot that must
        # rewrite inside a shared tail block writes into a private copy.
        # src/dst are traced int32 scalars, so this compiles once.
        self._cow_fn = None
        if self.prefix_cache:
            self._cow_fn = jax.jit(
                self._cow_impl, donate_argnums=(0,) if donate_cache else ())
        # fault-injection corruption (built only under a corrupting plan):
        # poison one pool block's bytes; _check_finite's watch detects it at
        # the sampling boundary and raises WireCorruption
        self._corrupt_fn = None
        self._finite_fn = None
        if self._nan_watch:
            self._corrupt_fn = jax.jit(
                self._corrupt_impl,
                donate_argnums=(0,) if donate_cache else ())
            self._finite_fn = jax.jit(
                lambda lg: jnp.isfinite(lg).all(axis=-1))
        self._reset()

    # ------------------------------------------------------------- state mgmt

    def _reset(self) -> None:
        self.prefix_index = (PrefixIndex(self.block_size)
                             if self.prefix_cache else None)
        self.allocator = BlockAllocator(self.n_blocks,
                                        prefix_index=self.prefix_index,
                                        shards=self.kv_shards)
        self._state = self._pin_state(
            init_paged_state(self.cfg, self.n_slots, self.n_blocks,
                             self.block_size, self.cache_dtype,
                             cache_spec=self.cache_spec))
        self._soft_reset()

    def _soft_reset(self) -> None:
        """Per-run scheduling state only: with ``persistent_cache`` the
        pools/allocator/index survive between runs (a clean previous run
        leaves every block free or parked in the index LRU), so the next
        run's matching prompts skip their shared prefill."""
        self._tables = np.zeros((self.n_slots, self.max_blocks), np.int32)
        self._lengths = np.zeros((self.n_slots,), np.int32)
        self._cur = np.zeros((self.n_slots,), np.int32)
        self._running: Dict[int, _Work] = {}
        self._waiting: List[_Work] = []
        # robustness bookkeeping (per run): step counter, fault-hold expiry,
        # stall/thrash guards (docs/serving.md §Failure modes & recovery)
        self._step_i = 0
        self._stall = 0
        # capacity telemetry (benchmarks/serve_throughput.py long-context
        # mode): peak per-slot context length and peak live pool blocks
        # observed over the run's steps
        self.max_resident_ctx = 0
        self.max_resident_blocks = 0
        self._hold_until = 0         # step at which fault-held blocks return
        self._step_preempts = 0
        self._preempt_window: collections.deque = collections.deque(
            maxlen=max(1, self.thrash_window))
        self._degraded = False

    def decode_cache_size(self) -> int:
        """Compiled-variant count of the program that advances decode (jit-
        stability witness: stays at 1 per gate variant however requests
        arrive and leave — 1 for a dense engine, ``len(gate_variants())``
        under an active policy). In mixed mode that program IS the unified
        step, summed over its gate variants."""
        if self._mixed_fns:
            return sum(fn._cache_size() for fn in self._mixed_fns.values())
        return self._decode._cache_size()

    def prefill_cache_size(self) -> int:
        """Compiled-variant count of the serving-path prefill program
        (mirror of ``decode_cache_size``). In mixed mode this counts the
        unified step programs (one per gate variant); with split chunked
        prefill, the single chunk program — both stay fixed across any mix
        of prompt lengths. On the whole-prompt path it sums the per-bucket
        programs (what the chunk program exists to collapse).
        ``measure_ttft``'s bucketed probes are excluded: they always go
        through the whole-prompt path and are not part of serving."""
        if self._mixed_fns:
            return sum(fn._cache_size() for fn in self._mixed_fns.values())
        if self._chunk_fn is not None:
            return self._chunk_fn._cache_size()
        return self._evicted_prefill_compiles + sum(
            fns[0]._cache_size() for fns in self._prefill_fns.values())

    def gate_variants(self) -> List[str]:
        """Names of the compiled mixed-step gate variants this engine
        dispatches between ("dense" always; "compressed" when the policy is
        active). Empty for split-scheduler engines (no mixed program)."""
        return [("compressed" if g else "dense")
                for g in sorted(self._mixed_fns)]

    def kv_pool_bytes(self, *, per_device: bool = False) -> int:
        """Bytes held by this engine's attention KV pools (payload + scales
        for quantized pools, dense dtype bytes otherwise).

        ``per_device=False`` (default) is the logical pool footprint — what
        the engine can address. ``per_device=True`` is what ONE device
        actually holds: with sequence-sharded pools each kv shard resides
        ``1/kv_shards`` of the blocks, so the same per-device HBM budget
        buys ``kv_shards`` times the addressable context."""
        return paged_cache_bytes(
            self.cfg, self.n_blocks, self.block_size,
            dtype_bytes=jnp.dtype(self.cache_dtype).itemsize,
            cache_spec=self.cache_spec, kv_shards=self.kv_shards,
            per_device=per_device)

    # ------------------------------------------------------- shape bucketing

    def _shapes_for(self, prompt_len: int):
        """(text bucket, total prefill length, blocks needed)."""
        cap = self.max_blocks * self.block_size - self._n_prefix
        if self._pad_ok:
            bucket = self.block_size
            while bucket < prompt_len:
                bucket *= 2
            bucket = min(bucket, cap)
        else:
            bucket = prompt_len
        if bucket < prompt_len:
            raise ValueError(
                f"prompt ({prompt_len} tokens) exceeds cache capacity ({cap})")
        total = bucket + self._n_prefix
        return bucket, total, -(-total // self.block_size)

    def _prefill_for(self, prompt_len: int):
        bucket, total, nb = self._shapes_for(prompt_len)
        if bucket in self._prefill_fns:
            self._prefill_fns.move_to_end(bucket)  # LRU touch
        else:
            model, ctx, dtype = self.model, self.ctx, self.cache_dtype

            def prefill(params, batch, last_index):
                cache = model.init_cache(1, total, dtype)
                return model.prefill(ctx, params, batch, cache,
                                     last_index=last_index)

            self._prefill_fns[bucket] = (
                jax.jit(prefill), self._make_insert(nb, total), total, nb)
            # bound the per-bucket program cache: hybrid archs specialize per
            # exact prompt length, which is unbounded without an LRU drop
            # (evicted compiles are remembered so prefill_cache_size stays a
            # true compile count, not a survivor count)
            while len(self._prefill_fns) > self.PREFILL_FN_CACHE_MAX:
                _, old = self._prefill_fns.popitem(last=False)
                self._evicted_prefill_compiles += old[0]._cache_size()
        return (bucket,) + self._prefill_fns[bucket]

    def _make_insert(self, nb: int, total: int):
        return jax.jit(self._insert_impl(nb, total),
                       donate_argnums=self._insert_donate)

    def _insert_impl(self, nb: int, total: int):
        """Prefill-insert body (jitted by ``_make_insert``; traced bare by
        ``trace_programs``): scatter a single-request dense prefill cache
        into the slot's allocated blocks / batched recurrent state rows.
        Quantized pools get the same scatter in wire format: the dense prefill
        K/V is MX-quantized per position before the block write."""
        bs, cfg = self.block_size, self.cfg
        cache_spec = self.cache_spec
        pad = nb * bs - total

        def insert(state, layer_caches, cross, slot, block_ids):
            pools_k = list(state["pools_k"])
            pools_v = list(state["pools_v"])
            rec = list(state["rec"])
            ai = ri = 0
            for i, spec in enumerate(cfg.layers):
                c = layer_caches[i]
                if spec.kind == "attn":
                    k = jnp.pad(c.k[0], ((0, pad), (0, 0))).reshape(nb, bs, -1)
                    v = jnp.pad(c.v[0], ((0, pad), (0, 0))).reshape(nb, bs, -1)
                    if cache_spec.quantized:
                        kq, vq = quantize_kv_pages(k, v, cache_spec.mx)
                        if self.ctx.kv_sharded:
                            # sharded pools: each kv shard writes only the
                            # blocks it owns and drops the rest (no wire)
                            kp, ks, vp, vs = pool_block_write(self.ctx, [
                                (pools_k[ai].payload, kq.payload),
                                (pools_k[ai].scales, kq.scales),
                                (pools_v[ai].payload, vq.payload),
                                (pools_v[ai].scales, vq.scales)], block_ids)
                            pools_k[ai] = constrain_wire_pool(
                                self.ctx, MXCompressed(payload=kp, scales=ks))
                            pools_v[ai] = constrain_wire_pool(
                                self.ctx, MXCompressed(payload=vp, scales=vs))
                        else:
                            pools_k[ai] = constrain_wire_pool(self.ctx, MXCompressed(
                                payload=pools_k[ai].payload.at[block_ids].set(kq.payload),
                                scales=pools_k[ai].scales.at[block_ids].set(kq.scales)))
                            pools_v[ai] = constrain_wire_pool(self.ctx, MXCompressed(
                                payload=pools_v[ai].payload.at[block_ids].set(vq.payload),
                                scales=pools_v[ai].scales.at[block_ids].set(vq.scales)))
                    elif self.ctx.kv_sharded:
                        pools_k[ai], pools_v[ai] = pool_block_write(self.ctx, [
                            (pools_k[ai], k.astype(pools_k[ai].dtype)),
                            (pools_v[ai], v.astype(pools_v[ai].dtype)),
                        ], block_ids)
                    else:
                        pools_k[ai] = pools_k[ai].at[block_ids].set(
                            k.astype(pools_k[ai].dtype))
                        pools_v[ai] = pools_v[ai].at[block_ids].set(
                            v.astype(pools_v[ai].dtype))
                    ai += 1
                else:
                    rec[ri] = jax.tree.map(
                        lambda sb, s1: sb.at[slot].set(s1[0].astype(sb.dtype)),
                        rec[ri], c)
                    ri += 1
            new = {**state, "pools_k": pools_k, "pools_v": pools_v, "rec": rec}
            if cross is not None:
                ck, cv = list(state["cross_k"]), list(state["cross_v"])
                for l in range(cfg.n_layers):
                    ck[l] = ck[l].at[slot].set(cross[l].k[0].astype(ck[l].dtype))
                    cv[l] = cv[l].at[slot].set(cross[l].v[0].astype(cv[l].dtype))
                new["cross_k"], new["cross_v"] = ck, cv
            return new

        return insert

    def _cow_impl(self, state, src, dst):
        """Copy block ``src``'s content to block ``dst`` in every attention
        layer's K/V pool (wire payload+scales pairs when quantized). Same
        constrain discipline as the other pool producers so downstream
        programs keep their compile-once input shardings. On sharded pools
        the fork is one masked-psum broadcast of the src block from its
        owner plus a drop-write at dst — one block of wire per pool plane,
        independent of capacity."""
        a = self.ctx.axis if self.ctx.tp else None
        if self.ctx.kv_sharded:
            pools = list(state["pools_k"]) + list(state["pools_v"])
            kv0 = self.ctx.kv_axis
            if self.cache_spec.quantized:
                planes = [pl for p in pools for pl in (p.payload, p.scales)]
                out = pool_block_copy(self.ctx, planes, src, dst)
                new = [constrain_wire_pool(self.ctx, MXCompressed(
                           payload=out[i], scales=out[i + 1]))
                       for i in range(0, len(out), 2)]
            else:
                out = pool_block_copy(self.ctx, pools, src, dst)
                new = [constrain(self.ctx, p, kv0, None, a) for p in out]
            n = len(state["pools_k"])
            return {**state, "pools_k": new[:n], "pools_v": new[n:]}
        copy1 = lambda p: (
            constrain_wire_pool(self.ctx, MXCompressed(
                payload=p.payload.at[dst].set(p.payload[src]),
                scales=p.scales.at[dst].set(p.scales[src])))
            if self.cache_spec.quantized
            else constrain(self.ctx, p.at[dst].set(p[src]), None, None, a))
        return {**state,
                "pools_k": [copy1(p) for p in state["pools_k"]],
                "pools_v": [copy1(p) for p in state["pools_v"]]}

    def _corrupt_impl(self, state, block):
        """Fault injection: poison block ``block`` in every attention
        layer's K/V pool. Wire pools get their e8m0 scale bytes maxed
        (255 -> 2^128, so dequant overflows to inf/NaN); dense pools get
        NaN directly. Same constrain discipline as the other pool
        producers, so the corrupted state re-enters the step programs
        without a recompile. On sharded pools only the shard owning
        ``block`` writes the poison (communication-free drop-write)."""
        a = self.ctx.axis if self.ctx.tp else None
        if self.ctx.kv_sharded:
            pools = list(state["pools_k"]) + list(state["pools_v"])
            kv0 = self.ctx.kv_axis
            n = len(state["pools_k"])
            if self.cache_spec.quantized:
                out = pool_block_fill(
                    self.ctx, [(p.scales, 255) for p in pools], block)
                new = [constrain_wire_pool(self.ctx, MXCompressed(
                           payload=p.payload, scales=s))
                       for p, s in zip(pools, out)]
            else:
                out = pool_block_fill(
                    self.ctx, [(p, jnp.nan) for p in pools], block)
                new = [constrain(self.ctx, p, kv0, None, a) for p in out]
            return {**state, "pools_k": new[:n], "pools_v": new[n:]}
        poison1 = lambda p: (
            constrain_wire_pool(self.ctx, MXCompressed(
                payload=p.payload,
                scales=p.scales.at[block].set(jnp.uint8(255))))
            if self.cache_spec.quantized
            else constrain(self.ctx, p.at[block].set(jnp.nan), None, None, a))
        return {**state,
                "pools_k": [poison1(p) for p in state["pools_k"]],
                "pools_v": [poison1(p) for p in state["pools_v"]]}

    def _check_finite(self, logits, rows: List[int]) -> None:
        """WireCorruption watch: raise if any row about to contribute a
        sampled token carries non-finite logits — poisoned pool bytes
        reached the sampling boundary. Runs BEFORE host state absorbs the
        step's tokens, so a supervisor replay starts from clean outputs.
        Enabled only under a corrupting fault plan (``_nan_watch``)."""
        if not self._nan_watch or not rows:
            return
        finite = np.asarray(self._finite_fn(logits))
        bad = [r for r in rows if not finite[r]]
        if bad:
            raise WireCorruption(
                f"non-finite logits at sampling row(s) {bad} (step "
                f"{self._step_i}) — a corrupted KV pool block reached the "
                f"sampling boundary; pools must be rebuilt (hard recovery)")

    # ------------------------------------------------------------- sampling

    @staticmethod
    def _sample_impl(logits, temps, key):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        keys = jax.random.split(key, logits.shape[0])
        safe = jnp.maximum(temps, 1e-6)[:, None]
        drawn = jax.vmap(jax.random.categorical)(keys, logits / safe)
        return jnp.where(temps > 0, drawn.astype(jnp.int32), greedy)

    # ------------------------------------------------------------ scheduling

    def _free_slot(self) -> Optional[int]:
        for s in range(self.n_slots):
            if s not in self._running:
                return s
        return None

    def _admit_ready(self, now: float) -> None:
        if self._degraded and self._running:
            # thrash degradation: stop feeding the storm — no admissions
            # until a retire clears the flag (admitting with nothing
            # running is always allowed, so degradation can never deadlock
            # an empty engine)
            return
        while self._waiting and self._waiting[0].arrival <= now:
            slot = self._free_slot()
            if slot is None:
                return
            w = self._waiting[0]
            if self.prefill_chunk:
                # chunked admission is cheap: just a slot — blocks arrive
                # incrementally as chunks land (_prefill_step), so a long
                # prompt no longer needs its whole KV footprint up front
                self._waiting.pop(0)
                self._admit_chunked(w, slot, now)
                continue
            _, _, _, _, nb = self._prefill_for(len(w.prompt))
            ids = self.allocator.alloc(nb)
            if ids is None:
                if not self._running:
                    if self.allocator.n_held:
                        return  # synthetic (fault-held) pressure: wait it out
                    raise PoolExhausted(
                        f"prefill needs {nb} KV blocks; only "
                        f"{self.allocator.n_free} free and nothing to evict — "
                        f"pool too small for this request")
                return  # decode will retire/evict slots and free blocks
            self._waiting.pop(0)
            self._admit(w, slot, ids)

    def _admit_chunked(self, w: _Work, slot: int, now: float) -> None:
        """Move a request into a slot in PREFILLING state; its prompt will
        stream into the pools ``prefill_chunk`` tokens per engine step. With
        the prefix cache on, cached prompt blocks are mapped into the slot's
        table first and chunking resumes at the first non-cached token."""
        w.blocks = []
        w.pos = 0
        w.prefilling = True
        self._clear_slot(slot)
        if self.prefix_index is not None:
            self._match_prefix(w, slot)
        if w.admitted_t is None:
            w.admitted_t = now
        self._running[slot] = w

    def _match_prefix(self, w: _Work, slot: int) -> None:
        """Map the longest indexed prefix of ``w.prompt`` into the slot.

        Matches are truncated to ``prefill_chunk`` multiples so the warm
        suffix recomputes with the same chunk boundaries as the original
        writer (bit-identical outputs in both cache modes). A FULL-prompt
        match must still recompute something — the engine needs last-token
        logits to sample the first output token:

        * exact pools (dense at the compute dtype): keep everything but the
          final token; the tail shared block is COW-forked into a private
          copy, since the chunk program rewrites position L-1 inside it.
          If the pool can't supply the fork block, the tail share is
          dropped instead (plain shorter match; never fails admission).
        * lossy pools (quantized, or cache_dtype below the compute dtype):
          resume at the last chunk-aligned boundary and recompute the whole
          tail chunk. A mid-chunk resume would read the final chunk's
          history at pool precision where the cold run attended it in
          compute precision — visibly different logits on fp4 pools; the
          aligned resume re-runs the writer's exact program instead."""
        L = len(w.prompt)
        bs = self.block_size
        w.hashes = PrefixIndex.chain(w.prompt, self.block_size)
        ids = self.prefix_index.match(w.hashes)
        # resume-point granularity: a multiple of both the block size (match
        # unit) and the chunk size (so warm chunk boundaries line up with
        # the writer's) — a full-prompt match skips the truncation and goes
        # through the COW path instead
        grain = math.lcm(bs, self.prefill_chunk)
        align = lambda blocks: blocks[:(len(blocks) * bs // grain) * grain // bs]
        if ids and len(ids) * bs < L:
            ids = align(ids)
        if not ids:
            return
        self.allocator.share(ids)
        w.blocks = list(ids)
        m_tok = len(w.blocks) * bs
        if m_tok >= L:  # full-prompt hit: recompute the last token's logits
            fork = self.allocator.alloc(1) if self._exact_pools else None
            if fork is not None:
                self._state = self._cow_fn(self._state,
                                           jnp.int32(w.blocks[-1]),
                                           jnp.int32(fork[0]))
                self.stats.record_dispatch(1)  # COW block fork
                self.allocator.release([w.blocks[-1]])
                w.blocks[-1] = fork[0]
                m_tok = L - 1
            else:  # lossy pools (or pool dry): resume at the last aligned
                   # boundary and recompute the whole tail chunk — exact in
                   # every cache mode, never fails admission
                keep = ((L - 1) // grain) * grain // bs
                self.allocator.release(w.blocks[keep:])
                del w.blocks[keep:]
                m_tok = keep * bs
                if not w.blocks:
                    return
        w.pos = m_tok
        w.cached_tokens += m_tok
        self.prefix_index.hit_blocks += len(w.blocks)
        self._tables[slot, :len(w.blocks)] = w.blocks
        self._lengths[slot] = w.pos

    def _alloc_for_chunk(self, slot: int, w: _Work, n_valid: int) -> bool:
        """Allocate the blocks covering ``n_valid`` more prompt tokens for a
        PREFILLING slot, evicting the latest-arrival request under pressure
        (LIFO). Returns False when the slot is itself the LIFO victim — it
        defers in place, keeping the chunks already written (self-preempting
        would discard them and churn through admit/preempt every step)
        while earlier-arrival decodes retire and free blocks."""
        need = -(-(w.pos + n_valid) // self.block_size)
        while True:
            got = self.allocator.alloc_to(w.blocks, need)
            if got is not None:
                self._tables[slot, need - len(got):need] = got
                return True
            victim = max(self._running,
                         key=lambda s: (self._running[s].arrival, s))
            if victim == slot:
                if len(self._running) == 1 and not self.allocator.n_held:
                    raise PoolExhausted(
                        f"prefill chunk needs {need - len(w.blocks)} KV "
                        f"blocks; only {self.allocator.n_available} "
                        f"available and nothing to evict — pool too small "
                        f"for this request")
                return False
            if self._step_preempts >= self.max_preempts_per_step:
                return False  # storm guard: defer instead of another victim
            self._preempt(victim)

    def _advance_prefill(self, slot: int, w: _Work, n_valid: int) -> None:
        """Account ``n_valid`` freshly-written prompt tokens: advance the
        slot's write position and publish every prompt block the tokens
        completed (hash j certifies tokens [0, (j+1)*bs), all now written
        and immutable)."""
        old_pos = w.pos
        w.pos += n_valid
        self._lengths[slot] = w.pos
        if self.prefix_index is not None:
            for j in range(old_pos // self.block_size,
                           min(w.pos // self.block_size, len(w.hashes))):
                self.prefix_index.register(w.hashes[j], w.blocks[j])

    def _first_token(self, slot: int, w: _Work, tok: int, now: float,
                     logits, row: int) -> None:
        """Prefill-complete bookkeeping, shared by every prefill flavor
        (final chunk in mixed/split mode, whole-prompt admission): the
        sampled token (drawn from ``logits[row]``) ends PREFILLING and is
        the TTFT endpoint."""
        w.prefilling = False
        self._cur[slot] = tok
        if w.first_token_t is None:
            w.first_token_t = now
            if self.keep_first_logits:
                w.req.first_logits = np.asarray(logits[row], np.float32)
        w.tokens.append(tok)
        w.token_times.append(now)
        if w.done:
            self._retire(slot, now)

    def _prefill_step(self) -> int:
        """Split-scheduler prefill: run ONE chunk for the earliest-arrival
        PREFILLING slot — the per-step prompt-token budget that keeps long
        prefills from stalling running decodes. Returns the number of
        prompt tokens processed (0 if no chunk ran)."""
        pref = [s for s, w in self._running.items() if w.prefilling]
        if not pref:
            return 0
        slot = min(pref, key=lambda s: (self._running[s].arrival, s))
        w = self._running[slot]
        L = len(w.prompt)
        n_valid = min(self.prefill_chunk, L - w.pos)
        if not self._alloc_for_chunk(slot, w, n_valid):
            return 0

        tokens = np.zeros((1, self.prefill_chunk), np.int32)
        tokens[0, :n_valid] = w.prompt[w.pos:w.pos + n_valid]
        logits, self._state = self._chunk_fn(
            self.params, jnp.asarray(tokens), self._state,
            jnp.asarray(self._tables[slot].copy()), jnp.int32(w.pos),
            jnp.int32(n_valid))
        self._advance_prefill(slot, w, n_valid)
        if w.pos >= L:
            # final chunk: its logits (read at the last real token) yield the
            # request's first sampled token, ending PREFILLING
            self._check_finite(logits, [0])
            self._key, sub = jax.random.split(self._key)
            temp = jnp.full((1,), w.req.temperature, jnp.float32)
            tok = int(np.asarray(self._sample(logits, temp, sub))[0])
            self._first_token(slot, w, tok, time.perf_counter() - self._t0,
                              logits, 0)
        return n_valid

    def _pack_prefill(self, budget: int) -> List:
        """Mixed-step budget packing: place PREFILLING slots' chunks,
        earliest arrival first, into the remaining budget (blocks allocated
        per slot, LIFO eviction under pressure). Returns
        ``(slot, chunk_tokens, start_pos)`` segments for
        ``build_mixed_batch``.

        Only FULL split-schedule chunks are packed — ``min(prefill_chunk,
        remaining prompt)``, exactly the chunk the split scheduler would
        run next, never a budget-truncated slice. Chunk boundaries decide
        which prompt tokens attend each other at compute precision (same
        chunk) vs pool precision (earlier chunk), so on lossy pools a
        truncated chunk would make outputs — and the bytes published to
        the prefix index — depend on packing timing; full-chunk packing
        keeps every slot's chunk schedule identical to the split engine's
        and the mixed-vs-split token parity structural. A chunk that
        doesn't fit the leftover budget just waits for the next step
        (the constructor floor ``token_budget >= max_slots +
        prefill_chunk`` guarantees the earliest-arrival slot always
        fits, so it can never be starved by later arrivals); a slot that
        can't get blocks defers without blocking the rest of the pack."""
        segs = []
        pref = sorted((s for s, w in self._running.items() if w.prefilling),
                      key=lambda s: (self._running[s].arrival, s))
        for slot in pref:
            if budget <= 0:
                break
            if slot not in self._running:   # evicted packing an earlier slot
                continue
            w = self._running[slot]
            n = min(self.prefill_chunk, len(w.prompt) - w.pos)
            if n > budget:      # never truncate: wait for the next step
                continue
            if n <= 0 or not self._alloc_for_chunk(slot, w, n):
                continue
            segs.append((slot, w.prompt[w.pos:w.pos + n], w.pos))
            budget -= n
            if self._degraded:
                # thrash degradation: one chunk per step (the split-
                # scheduler rate) until a retire clears the storm — fewer
                # REAL tokens in the same fixed-shape program, so no
                # recompile
                break
        return segs

    def _step_mixed(self) -> int:
        """One unified engine step: pack prefill chunks + the decode batch
        into a single flattened token-budget program dispatch, then sample
        every slot that produced a token this step. Returns the number of
        real tokens processed (0: every slot deferred). Its four phases are
        disjoint spans (docs/serving.md "Observability")."""
        span = self.stats.span
        with span("engine.schedule"):
            self._grow_or_evict()
            decoding = sorted(s for s, w in self._running.items()
                              if not w.prefilling)
            # decode tokens are reserved FIRST (never dropped for prefill
            # work; token_budget >= n_slots guarantees they fit), prefill
            # chunks pack into the remainder
            segs = self._pack_prefill(self.token_budget - len(decoding))
            # eviction during packing may have preempted decode slots
            decoding = [s for s in decoding if s in self._running]
            if not segs and not decoding:
                return 0  # every prefilling slot deferred; decodes free blocks
            batch = build_mixed_batch(
                segs, [(s, int(self._cur[s]), int(self._lengths[s]))
                       for s in decoding],
                self.token_budget, self.n_slots)

            # per-step compression gate on the batch's REAL composition
            # (n_prefill/n_decode count valid tokens, never padding):
            # dispatch the pre-compiled variant; no shape changes, so no
            # recompile
            gate = (True in self._mixed_fns
                    and self._gate_policy.active_for_step(batch.n_prefill,
                                                          batch.n_decode))
        with span("engine.dispatch"):
            # the scheduler's host tables are mutated in place right after
            # this asynchronous dispatch (lengths advance below), so they go
            # in as host copies taken now: on the CPU backend the transfer
            # may read its numpy buffer after the call returns, and even
            # jnp.array's copy then races the host's writes
            logits, self._state = self._mixed_fns[gate](
                self.params, jnp.asarray(batch.tokens), self._state,
                jnp.asarray(batch.slot_ids), jnp.asarray(batch.positions),
                jnp.asarray(batch.valid), jnp.asarray(batch.is_decode),
                jnp.asarray(self._lengths.copy()),
                jnp.asarray(self._tables.copy()),
                jnp.asarray(batch.sample_idx))
            self.gate_counts["compressed" if gate else "dense"] += 1
            self.stats.record_step(
                batch.n_prefill, batch.n_decode, n_dispatches=1,
                compressed=gate,
                read_tiles=(read_tiles_used(
                    [len(toks) for _, toks, _ in segs] + [1] * len(decoding))
                    if self._read_tiles_held else 0),
                read_tiles_held=self._read_tiles_held)

        with span("engine.wait"):
            # one sample over all slots; non-sampling rows are
            # garbage/discarded
            temps = np.zeros((self.n_slots,), np.float32)
            for slot, _, _ in segs:
                temps[slot] = self._running[slot].req.temperature
            for slot in decoding:
                self._lengths[slot] += 1
                temps[slot] = self._running[slot].req.temperature
            self._key, sub = jax.random.split(self._key)
            # corruption watch runs before ANY host state absorbs this
            # step's tokens, so a supervisor replay never sees poisoned
            # output
            self._check_finite(logits, decoding + [
                slot for slot, chunk, _ in segs
                if self._running[slot].pos + len(chunk)
                >= len(self._running[slot].prompt)])
            toks = np.asarray(self._sample(logits, jnp.asarray(temps), sub))

        with span("engine.emit"):
            now = time.perf_counter() - self._t0
            for slot, chunk, _ in segs:
                w = self._running[slot]
                self._advance_prefill(slot, w, len(chunk))
                if w.pos >= len(w.prompt):
                    # final chunk: its sampled row is the request's first
                    # token
                    self._first_token(slot, w, int(toks[slot]), now, logits,
                                      slot)
            for slot in decoding:
                w = self._running[slot]
                tok = int(toks[slot])
                w.tokens.append(tok)
                w.token_times.append(now)
                self._cur[slot] = tok
                if w.done:
                    self._retire(slot, now)
        return batch.n_prefill + batch.n_decode

    def _admit(self, w: _Work, slot: int, ids: List[int]) -> None:
        _, prefill, insert, total, nb = self._prefill_for(len(w.prompt))
        L = len(w.prompt)
        bucket = total - self._n_prefix
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :L] = w.prompt
        batch = {"tokens": jnp.asarray(tokens), **w.extra}
        last_index = jnp.int32(self._n_prefix + L - 1)

        logits, cache = prefill(self.params, batch, last_index)
        # whole-prompt prefill + insert, processing the prompt off-step
        self.stats.record_dispatch(2, prefill_tokens=L)
        self._check_finite(logits, [0])
        self._key, sub = jax.random.split(self._key)
        temp = jnp.full((1,), w.req.temperature, jnp.float32)
        tok = int(np.asarray(self._sample(logits, temp, sub))[0])
        self._state = insert(self._state, cache["layers"], cache.get("cross"),
                             jnp.int32(slot), jnp.asarray(ids, np.int32))

        now = time.perf_counter() - self._t0
        w.blocks = ids
        self._tables[slot, :] = 0
        self._tables[slot, :nb] = ids
        self._lengths[slot] = self._n_prefix + L
        if w.admitted_t is None:
            w.admitted_t = now
        self._running[slot] = w
        self._first_token(slot, w, tok, now, logits, 0)

    def _grow_or_evict(self) -> None:
        """Give every DECODING slot a block covering its next write position,
        preempting the latest-arrival request when the pool runs dry.
        PREFILLING slots allocate their own blocks as chunks land
        (_prefill_step); their masked decode writes fall into the null block
        until then."""
        decoding = [s for s in self._running if not self._running[s].prefilling]
        for slot in sorted(decoding, key=lambda s: self._running[s].arrival):
            if slot not in self._running:  # preempted by an earlier iteration
                continue
            w = self._running[slot]
            while len(w.blocks) * self.block_size <= self._lengths[slot]:
                got = self.allocator.alloc(1)
                if got is None:
                    victim = max(self._running,
                                 key=lambda s: (self._running[s].arrival, s))
                    if (victim == slot and len(self._running) == 1
                            and not self.allocator.n_held):
                        raise PoolExhausted(
                            "KV pool exhausted with a single request in "
                            "flight — n_blocks too small for prompt+decode")
                    # under fault-held pressure the sole request self-
                    # preempts instead: it requeues, the hold expires on
                    # schedule, and readmission recomputes. Decode slots
                    # cannot defer in place (the next write needs a real
                    # block), so growth preemption ignores the per-step
                    # budget — the thrash window still counts it.
                    self._preempt(victim)
                    if victim == slot:
                        break
                    continue
                w.blocks += got
                self._tables[slot, len(w.blocks) - 1] = got[0]

    def _preempt(self, slot: int) -> None:
        """Evict-and-recompute: free the slot, fold generated tokens into the
        prompt, and requeue; the readmission prefill rebuilds the KV. A
        PREFILLING victim simply restarts its prompt from chunk 0."""
        w = self._running.pop(slot)
        self.allocator.release(w.blocks)  # shared blocks survive in the
        w.blocks = []                     # index for the readmission match
        w.prefilling = False
        w.pos = 0
        w.hashes = None
        self._clear_slot(slot)
        w.prompt = np.concatenate(
            [np.asarray(w.req.prompt, np.int32),
             np.asarray(w.tokens, np.int32)])
        w.preemptions += 1
        self._step_preempts += 1
        bisect.insort(self._waiting, w, key=lambda x: x.arrival)

    def _clear_slot(self, slot: int) -> None:
        self._tables[slot, :] = 0
        self._lengths[slot] = 0
        self._cur[slot] = 0

    def _retire(self, slot: int, now: float) -> None:
        self._finish(slot, OUTCOME_OK, now)

    def _finish(self, slot: int, outcome: str, now: float) -> None:
        """Terminal exit for a RUNNING slot, for any outcome: release the
        slot's blocks (mid-decode cancellation/timeout included — shared
        prefix blocks survive in the index for other requests), clear the
        host tables, and record the timing. An ``ok`` retire also clears
        thrash degradation: a completed request IS forward progress."""
        w = self._running.pop(slot)
        self.allocator.release(w.blocks)
        w.blocks = []
        self._clear_slot(slot)
        self._record_terminal(w, outcome, now)
        if outcome == OUTCOME_OK:
            self._degraded = False

    def _record_terminal(self, w: _Work, outcome: str, now: float) -> None:
        """Fill the request's output/timing at its terminal outcome.
        Degraded outcomes keep whatever tokens were generated (partial
        output) — callers must already have released any blocks."""
        r = w.req
        gen = w.tokens[: r.max_new_tokens]
        r.output = np.asarray(gen, np.int32)
        r.timing = RequestTiming(
            arrival_s=w.arrival, admitted_s=w.admitted_t,
            first_token_s=w.first_token_t, finished_s=now,
            n_prompt=len(np.asarray(r.prompt)), n_generated=len(gen),
            n_preemptions=w.preemptions, n_cached_prompt=w.cached_tokens,
            inter_token_s=[b - a for a, b in zip(w.token_times,
                                                 w.token_times[1:])],
            outcome=outcome)
        r.ttft_s = (r.timing.ttft_s if w.first_token_t is not None else None)
        r.latency_s = r.timing.latency_s
        self.stats.record(r.timing)

    def _expired(self, w: _Work, now: float) -> Optional[str]:
        """The terminal outcome ``w`` should leave with right now, or None.
        Cancellation wins over deadlines; deadlines measure from arrival
        (engine defaults unless the request overrides), and the TTFT
        deadline stops applying once a first token exists."""
        if w.req.cancelled:
            return OUTCOME_CANCELLED
        if w.arrival > now:
            return None  # not in the system yet
        d = (w.req.deadline_s if w.req.deadline_s is not None
             else self.deadline_s)
        if d is not None and now - w.arrival >= d and not w.done:
            return OUTCOME_TIMED_OUT
        dt = (w.req.deadline_ttft_s if w.req.deadline_ttft_s is not None
              else self.deadline_ttft_s)
        if (dt is not None and w.first_token_t is None
                and now - w.arrival >= dt):
            return OUTCOME_TIMED_OUT
        return None

    def _sweep_terminal(self, now: float) -> None:
        """Once per loop iteration, before admission: move every cancelled /
        deadline-expired request (waiting or running) to its terminal
        outcome."""
        kept: List[_Work] = []
        for w in self._waiting:  # filtering keeps arrival order (sorted)
            oc = self._expired(w, now)
            if oc is None:
                kept.append(w)
            else:
                self._record_terminal(w, oc, now)
        self._waiting = kept
        for slot in list(self._running):
            oc = self._expired(self._running[slot], now)
            if oc is not None:
                self._finish(slot, oc, now)

    def _bound_queue(self, now: float) -> None:
        """Admission backpressure, enforced AFTER admission has filled every
        free slot: arrived requests that were never admitted, beyond the
        newest ``max_queue`` the queue can absorb, leave as REJECTED.
        Preempted requeues were already accepted and are exempt — they
        re-enter a slot or time out, never reject."""
        if self.max_queue is None:
            return
        arrived = [w for w in self._waiting
                   if w.arrival <= now and w.admitted_t is None]
        drop = arrived[self.max_queue:]
        if drop:
            ids = {id(w) for w in drop}
            self._waiting = [w for w in self._waiting if id(w) not in ids]
            for w in drop:
                self._record_terminal(w, OUTCOME_REJECTED, now)

    # ------------------------------------------------------ faults & recovery

    def _apply_faults(self) -> None:
        """Fire the fault plan's events due at this step (serving/faults.py
        documents the kinds) and expire previous holds. Host-side only: the
        one device-touching fault is delegated to ``_corrupt_block``."""
        if self._hold_until and self._step_i >= self._hold_until:
            self.allocator.unhold()
            self._hold_until = 0
        for f in self.fault_plan.take(self._step_i):
            if f.kind == "exhaust":
                self.allocator.hold(f.n_blocks)
                self._hold_until = max(self._hold_until,
                                       self._step_i + f.duration)
            elif f.kind == "corrupt":
                self._corrupt_block(f.block)
            elif f.kind == "slow":
                time.sleep(f.sleep_s)
            elif f.kind == "stuck":
                time.sleep(max(f.sleep_s,
                               2.0 * (self.step_timeout_s or 0.05)))
            elif f.kind == "die":
                raise EngineDead(
                    f"fault injection: engine died at step {self._step_i} "
                    f"with {len(self._running)} in-flight and "
                    f"{len(self._waiting)} queued request(s)")

    def _corrupt_block(self, block: int) -> None:
        """Poison one live pool block (the lowest live block when ``block``
        is -1; silently a no-op when nothing is live — there is nothing to
        corrupt)."""
        live = sorted(b for w in self._running.values() for b in w.blocks)
        if block < 0:
            if not live:
                return
            block = live[0]
        self._state = self._corrupt_fn(self._state, jnp.int32(block))

    def recover(self, *, hard: bool = True) -> None:
        """Restore the engine to a runnable state after ``run`` aborted with
        ``EngineDead`` / ``StepStuck`` / ``WireCorruption`` (the
        ``EngineSupervisor`` calls this between attempts).

        ``hard=True`` (required for EngineDead/WireCorruption — device
        pools are lost or poisoned): discard everything; the next ``run()``
        rebuilds pools, allocator, and prefix index from scratch.
        ``hard=False`` (StepStuck on a ``persistent_cache`` engine — pools
        are healthy): release the in-flight requests' blocks and keep the
        pools and prefix index warm, so replayed requests re-hit their
        cached prefixes."""
        if hard or not self.persistent_cache:
            self._ran = False        # next run() takes the full _reset path
            self._soft_reset()
            return
        for slot in list(self._running):
            w = self._running.pop(slot)
            self.allocator.release(w.blocks)
            w.blocks = []
        self.allocator.unhold()      # expire any fault holds mid-flight
        self._soft_reset()

    def _decode_once(self) -> int:
        """One batched decode step over every DECODING slot. PREFILLING slots
        ride along shape-stably: their (garbage) write lands at
        ``lengths[slot]`` — the next chunk's first position, which the chunk
        program overwrites before any read, or the null block when that
        block isn't allocated yet — and their sampled token is discarded.
        Returns the number of decode tokens sampled."""
        logits, self._state = self._decode(
            self.params, jnp.asarray(self._cur[:, None].copy()), self._state,
            jnp.asarray(self._tables.copy()),
            jnp.asarray(self._lengths.copy()))
        active = [(s, w) for s, w in self._running.items() if not w.prefilling]
        temps = np.zeros((self.n_slots,), np.float32)
        for slot, w in active:
            self._lengths[slot] += 1
            temps[slot] = w.req.temperature
        self._check_finite(logits, [s for s, _ in active])
        self._key, sub = jax.random.split(self._key)
        toks = np.asarray(self._sample(logits, jnp.asarray(temps), sub))
        now = time.perf_counter() - self._t0
        for slot, w in active:
            tok = int(toks[slot])
            w.tokens.append(tok)
            w.token_times.append(now)
            self._cur[slot] = tok
            if w.done:
                self._retire(slot, now)
        return len(active)

    # ------------------------------------------------------------------ API

    def run(self, requests: List[Request], *, extra_inputs: Optional[Dict] = None,
            seed: int = 0) -> List[Request]:
        """Serve ``requests``; returns them with output/ttft/latency filled.

        ``arrival_s`` offsets are honored against the run's wall clock, so
        staggered traffic exercises true continuous batching: late arrivals
        join slots that earlier requests have already vacated or still hold.
        ``extra_inputs`` are full-batch arrays (one row per request) that are
        sliced per request at prefill (vision patches, encoder frames).

        With ``persistent_cache=True`` the paged pools, allocator, and
        prefix index carry over from the previous ``run()`` (scheduling
        state and per-run stats still reset), so repeated system prompts
        skip their prefill across calls.
        """
        if self.persistent_cache and getattr(self, "_ran", False):
            self._soft_reset()
        else:
            self._reset()
        self._ran = True
        self.stats = ServeStats()
        self.gate_counts = {"compressed": 0, "dense": 0}
        self._key = jax.random.PRNGKey(seed)
        self._t0 = time.perf_counter()
        works = []
        capacity = self.max_blocks * self.block_size
        for i, r in enumerate(requests):
            need = self._n_prefix + len(np.asarray(r.prompt)) + r.max_new_tokens - 1
            if need > capacity:
                raise InvalidRequest(
                    f"request {i}: prompt+decode needs {need} cache positions "
                    f"but max_len={self.max_len} provides {capacity}")
            extra = {k: jnp.asarray(v[i:i + 1])
                     for k, v in (extra_inputs or {}).items()}
            works.append(_Work(req=r, prompt=np.asarray(r.prompt, np.int32),
                               extra=extra, arrival=float(r.arrival_s)))
        self._waiting = sorted(works, key=lambda w: w.arrival)

        span = self.stats.span
        try:
            while self._waiting or self._running:
                now = time.perf_counter() - self._t0
                with span("engine.admit"):
                    self._sweep_terminal(now)
                    self._admit_ready(now)
                    self._bound_queue(now)
                if not self._running:
                    with span("engine.idle"):
                        if self._waiting:
                            time.sleep(min(max(self._waiting[0].arrival - now,
                                               0.0), 0.005))
                    continue
                self._step_i += 1
                self._step_preempts = 0
                # the step's span is its one timer: the watchdog reads it
                with span("engine.step", step_num=self._step_i) as step:
                    if self.fault_plan is not None:
                        self._apply_faults()
                    if self.token_budget:
                        # unified step: packed prefill chunks + the decode
                        # batch in ONE program dispatch (DESIGN.md §Mixed
                        # step)
                        n_tok = self._step_mixed()
                    else:
                        # split scheduler: (at most) one prefill chunk, then
                        # a batched decode for every live DECODING slot —
                        # kills head-of-line blocking like the mixed step,
                        # at two dispatches per step
                        n_pref = (self._prefill_step() if self.prefill_chunk
                                  else 0)
                        self._grow_or_evict()
                        n_dec = 0
                        if any(not w.prefilling
                               for w in self._running.values()):
                            n_dec = self._decode_once()
                        self.stats.record_step(
                            n_pref, n_dec,
                            n_dispatches=(1 if n_pref else 0)
                            + (1 if n_dec else 0))
                        n_tok = n_pref + n_dec
                self._guard_step(n_tok, step.seconds)
        finally:
            # fault holds never outlive a run: whether it completed, timed
            # every request out, or is about to be supervised through a
            # recovery, the free list must conserve the pool
            if self.allocator.n_held:
                self.allocator.unhold()
                self._hold_until = 0
        return requests

    def _guard_step(self, n_tok: int, elapsed_s: float) -> None:
        """Post-step robustness checks: the step watchdog (wall time past
        ``step_timeout_s`` raises StepStuck — a post-hoc stand-in for the
        async watchdog thread a live server would run), the stall guard
        (``stall_limit`` consecutive zero-token steps with requests in
        flight raises StepStuck; fault-held pool pressure is exempt since
        it expires on schedule), and the thrash detector (preemptions over
        the rolling window past ``thrash_limit`` set degraded mode). Also
        records the run's capacity peaks (``max_resident_ctx`` /
        ``max_resident_blocks``)."""
        self.max_resident_ctx = max(self.max_resident_ctx,
                                    int(self._lengths.max(initial=0)))
        self.max_resident_blocks = max(
            self.max_resident_blocks,
            self.n_blocks - 1 - self.allocator.n_free)
        if self.step_timeout_s is not None and elapsed_s > self.step_timeout_s:
            raise StepStuck(
                f"engine step {self._step_i} took {elapsed_s:.3f}s "
                f"(step_timeout_s={self.step_timeout_s}); treating the "
                f"step loop as wedged")
        if n_tok > 0:
            self._stall = 0
        elif not self.allocator.n_held:
            self._stall += 1
            if self.stall_limit and self._stall >= self.stall_limit:
                raise StepStuck(
                    f"no token progress for {self._stall} consecutive "
                    f"steps with {len(self._running)} slot(s) in flight — "
                    f"scheduler livelock")
        self._preempt_window.append(self._step_preempts)
        if (not self._degraded
                and sum(self._preempt_window) >= self.thrash_limit):
            self._degraded = True

    def measure_ttft(self, prompt_len: int, *, iters: int = 8,
                     extra_inputs: Optional[Dict] = None) -> Dict[str, float]:
        """Median prefill TTFT at a given prompt length (Table 3 metric),
        measured through the bucketed prefill the engine actually serves."""
        prompt = np.random.default_rng(0).integers(
            0, self.cfg.vocab_size, (prompt_len,), dtype=np.int64
        ).astype(np.int32)
        _, prefill, _, total, _ = self._prefill_for(prompt_len)
        bucket = total - self._n_prefix
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :prompt_len] = prompt
        batch = {"tokens": jnp.asarray(tokens)}
        if extra_inputs:
            batch.update({k: jnp.asarray(v[0:1]) for k, v in extra_inputs.items()})
        last_index = jnp.int32(self._n_prefix + prompt_len - 1)
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            logits, _cache = prefill(self.params, batch, last_index)
            logits.block_until_ready()
            times.append(time.perf_counter() - t0)
        if len(times) > 1:
            times = times[1:]  # drop the compile iteration (keep the only
                               # sample when iters == 1 rather than go NaN)
        times = np.array(times)
        return {"median_s": float(np.median(times)),
                "std_s": float(np.std(times)), "iters": len(times)}

    def step_program_text(self, *, compressed: bool = False) -> str:
        """Compiled HLO text of one gate variant of the mixed step program,
        lowered for this engine's parameters and pools: what the device
        runs (its kernels appear as ``tpu_custom_call``, its compressed
        collectives as uint8 all-gathers). Nothing executes."""
        if compressed not in self._mixed_fns:
            raise ValueError(f"no {'compressed' if compressed else 'dense'} "
                             f"mixed step; this engine has "
                             f"{self.gate_variants() or 'no mixed step'}")
        T, ns = self.token_budget, self.n_slots
        i32 = lambda *shape: np.zeros(shape, np.int32)
        flag = np.zeros((T,), bool)
        return self._mixed_fns[compressed].lower(
            self.params, i32(1, T), self._state, i32(T), i32(T), flag, flag,
            i32(ns), i32(ns, self.max_blocks), i32(ns)).compile().as_text()

    # ------------------------------------------------------- static analysis

    def _wire_tokens(self, batch: int, seq: int, ctx: TPContext) -> int:
        """Tokens crossing the wire per TP collective in a program whose
        activations are (batch, seq, d) — mirrors ``row_linear``'s
        ``n_tokens`` math (batch divides over the data axes when it can),
        so the auditor gates compression exactly where the model code does."""
        n = batch * seq
        if ctx.mesh is not None and ctx.data_axes and batch % ctx.dp_size == 0:
            n //= max(1, ctx.dp_size)
        return n

    def trace_programs(self, *, prompt_len: Optional[int] = None):
        """ClosedJaxprs of every compiled engine program, traced with
        ShapeDtypeStruct stand-ins — nothing executes on device.

        Returns ``{name: ProgramTrace}`` covering the programs this engine
        configuration actually dispatches: ``decode`` always; ``chunk``
        (split scheduler) or ``mixed`` (token-budget scheduler) per mode;
        ``cow`` when the prefix cache is on; and the whole-prompt
        ``prefill``/``insert`` pair for whole-prompt engines (or any engine
        when ``prompt_len`` is passed — chunked engines only reach that pair
        via ``measure_ttft``). This is the input surface of
        ``repro.staticcheck.jaxpr_audit``; the traces carry the policy,
        per-step wire-token count, and boundary avals each audit rule needs.
        """
        from repro.staticcheck.report import ProgramTrace

        sds = jax.ShapeDtypeStruct
        i32, b8 = jnp.int32, jnp.bool_
        aval = lambda x: sds(x.shape, x.dtype)
        state_in = jax.tree.map(aval, self._state)
        axis_sizes = dict(self.ctx.mesh.shape) if self.ctx.mesh else {}
        # pool leaf avals + read-path mode for the pool-gather rule: on a
        # use_pallas engine no step program may gather a pool at full
        # capacity (the kernel streams blocks instead)
        pool_avals = tuple(
            (tuple(l.shape), str(l.dtype))
            for key in ("pools_k", "pools_v")
            for l in jax.tree_util.tree_leaves(self._state[key]))
        traces = {}

        def trace(name, fn, args, *, ctx, n_tokens, is_step,
                  outs="logits+state", prefill_dominated=False):
            jaxpr, out = jax.make_jaxpr(fn, return_shape=True)(*args)
            logits = state_out = None
            if outs == "logits+state":
                logits, state_out = out
            elif outs == "logits":
                logits = out[0] if isinstance(out, tuple) else out
            elif outs == "state":
                state_out = out
            traces[name] = ProgramTrace(
                name=name, jaxpr=jaxpr, policy=ctx.policy, n_tokens=n_tokens,
                compute_dtype=str(jnp.dtype(self.cfg.dtype)), is_step=is_step,
                axis_sizes=axis_sizes, tp_axis=self.ctx.axis,
                logits_out=logits,
                state_in=state_in if state_out is not None else None,
                state_out=state_out,
                retrace=lambda: jax.make_jaxpr(fn)(*args),
                pool_avals=pool_avals,
                kernel_read_path=self.cache_spec.use_pallas,
                kv_shards=self.kv_shards, kv_axis=self.ctx.kv_axis,
                prefill_dominated=prefill_dominated)

        model, cache_spec = self.model, self.cache_spec
        tables = sds((self.n_slots, self.max_blocks), i32)
        lengths = sds((self.n_slots,), i32)

        trace("decode",
              lambda p, t, s, tb, ln: model.decode_step_paged(
                  self.ctx_decode, p, t, s, tb, ln, cache_spec=cache_spec),
              (self.params, sds((self.n_slots, 1), i32), state_in, tables,
               lengths),
              ctx=self.ctx_decode, is_step=True,
              n_tokens=self._wire_tokens(self.n_slots, 1, self.ctx_decode))

        if self._chunk_fn is not None:
            trace("chunk",
                  lambda p, t, s, row, st, nv: model.prefill_chunk(
                      self.ctx, p, t, s, row, st, nv, cache_spec=cache_spec),
                  (self.params, sds((1, self.prefill_chunk), i32), state_in,
                   sds((self.max_blocks,), i32), sds((), i32), sds((), i32)),
                  ctx=self.ctx, is_step=True,
                  n_tokens=self._wire_tokens(1, self.prefill_chunk, self.ctx))

        if self._mixed_fns:
            T = self.token_budget
            mixed_args = (self.params, sds((1, T), i32), state_in,
                          sds((T,), i32), sds((T,), i32), sds((T,), b8),
                          sds((T,), b8), lengths, tables,
                          sds((self.n_slots,), i32))
            # one trace per gate variant. "mixed" is the variant that serves
            # prefill-dominated steps (the compressed one when the policy is
            # active) — it carries prefill_dominated=True so the auditor's
            # missing-compression rule can demand the thesis be PRESENT.
            # n_tokens is the trace-time (padded) count: it describes what
            # the compiled program does; the REAL-count gate runs host-side
            # in _step_mixed by choosing between these variants.
            for name, gate in [("mixed", max(self._gate_ctxs)),
                               ("mixed-dense", False)]:
                if name == "mixed-dense" and True not in self._gate_ctxs:
                    break  # single-variant engine: "mixed" already covers it
                gctx = self._gate_ctxs[gate]
                trace(name,
                      lambda p, t, s, sid, pos, va, dec, st, tb, si,
                             _ctx=gctx:
                          model.mixed_step(_ctx, p, t, s, sid, pos, va, dec,
                                           st, tb, si,
                                           cache_spec=cache_spec),
                      mixed_args,
                      ctx=gctx, is_step=True,
                      n_tokens=self._wire_tokens(1, T, gctx),
                      prefill_dominated=(name == "mixed"))

        if self._cow_fn is not None:
            trace("cow", self._cow_impl,
                  (state_in, sds((), i32), sds((), i32)),
                  ctx=self.ctx, is_step=False, n_tokens=0, outs="state")

        if prompt_len is None and not self.prefill_chunk:
            prompt_len = self.block_size
        if prompt_len is not None:
            from repro.configs.base import InputShape

            bucket, total, nb = self._shapes_for(prompt_len)
            batch = self.model.input_specs(
                InputShape(name="audit", seq_len=total, global_batch=1,
                           kind="prefill"),
                dtype=jnp.dtype(self.cfg.dtype))
            cache0 = jax.eval_shape(
                lambda: model.init_cache(1, total, self.cache_dtype))

            def prefill(p, b, last):
                cache = model.init_cache(1, total, self.cache_dtype)
                return model.prefill(self.ctx, p, b, cache, last_index=last)

            trace("prefill", prefill,
                  (self.params, batch, sds((), i32)),
                  ctx=self.ctx, is_step=False, outs="logits",
                  n_tokens=self._wire_tokens(1, bucket, self.ctx))
            trace("insert", self._insert_impl(nb, total),
                  (state_in, cache0["layers"], cache0.get("cross"),
                   sds((), i32), sds((nb,), i32)),
                  ctx=self.ctx, is_step=False, n_tokens=0, outs="state")
        return traces
