"""Serving driver: continuous-batching requests through the Engine with
compressed TP (see DESIGN.md for the engine architecture).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
      --slots 4 --requests 8 --prompt-len 64 --new-tokens 16 --policy mx \
      --stagger 0.05

``--cache-spec`` selects the paged KV pool storage format: ``bf16`` (dense,
default) or an MX scheme (``fp4_e2m1``, or a full name like
``fp5_e2m2_b16_e8m0``) that stores K/V blocks in wire format — ~4x more
resident KV blocks in the same HBM at a small quantization cost
(DESIGN.md §Quantized cache).

``--prefill-chunk`` sets the per-slot prompt-token budget for chunked
prefill (DESIGN.md §Chunked prefill): prompts stream into the paged pools
chunk by chunk, interleaved with batched decode, instead of stalling every
running decode for a whole-prompt prefill. 0 forces whole-prompt prefill.

``--token-budget`` sizes the unified mixed-batch step (DESIGN.md §Mixed
step): each engine step flattens up to this many tokens — several slots'
prefill chunks plus every decode token — into ONE program dispatch
(default ``prefill_chunk + slots``; 0 keeps the split chunk-then-decode
scheduler for comparison).

``--min-prefill-fraction`` / ``--overlap-chunks`` tune the per-step
compression gate (DESIGN.md §Gating): under an active policy the mixed
engine compiles a dense and a compressed variant of its step program and
dispatches per step on the batch's real composition — compressed when
prefill tokens clear the fraction gate, dense otherwise. ``--overlap-chunks``
splits each compressed payload along the feature dim into a two-stage
quantize/gather pipeline (bit-identical to unchunked).

``--prefix-cache 1`` turns on automatic prefix caching (docs/serving.md):
requests whose prompts share a prefix (system prompts, few-shot templates)
map the shared KV blocks by reference instead of recomputing prefill —
needs chunked prefill, i.e. a pure-attention arch.

``--deadline-ms`` / ``--ttft-deadline-ms`` attach per-request SLOs: a
request that misses its deadline is cancelled mid-decode (blocks released,
partial output kept) and recorded as ``timed_out`` instead of crashing or
hogging a slot (docs/serving.md §Failure modes).

``--fault-plan`` injects deterministic faults ('exhaust@6x4;die@12' — see
``serving/faults.py`` for the grammar) and wraps the run in an
``EngineSupervisor`` that detects engine death / wire corruption / stuck
steps, rebuilds the pools, and replays unfinished requests with backoff.

Parameters are initialised under jit straight into their TP shards (no
device ever holds the whole model), and compiled programs persist in JAX's
compilation cache: ``JAX_COMPILATION_CACHE_DIR`` where set, else
``.jax_cache/`` at the checkout root.
"""
import argparse
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_config, reduced_config
from repro.core.formats import MXSpec
from repro.core.policy import CompressionPolicy, NO_COMPRESSION
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_kv_mesh
from repro.launch.sharding import init_params_sharded, make_context
from repro.models.frontends import audio_frames_stub, patch_embed_stub
from repro.models.model import Model
from repro.serving import Engine, EngineSupervisor, FaultPlan, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", "--batch", dest="slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=0,
                    help="total requests (default: one per slot)")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--policy", default="mx", choices=["mx", "none"])
    ap.add_argument("--variant", default="gather", choices=["gather", "two_phase"])
    ap.add_argument("--min-prefill-fraction", type=float, default=0.5,
                    help="per-step compression gate: a mixed step dispatches "
                         "the compressed program variant only when at least "
                         "this fraction of its REAL (non-padding) tokens are "
                         "prefill (0.0 = compress any step clearing the "
                         "policy's min_tokens; DESIGN.md §Gating)")
    ap.add_argument("--overlap-chunks", type=int, default=1,
                    help="split each compressed collective payload into this "
                         "many feature-dim chunks so chunk k+1's quantize "
                         "overlaps chunk k's transfer (two-stage gather; 1 = "
                         "unchunked, bit-identical results either way)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--cache-spec", default="bf16",
                    help="KV pool storage: 'bf16' (dense) or an MX scheme "
                         "('fp4_e2m1', 'fp5_e2m2_b16_e8m0', ...)")
    ap.add_argument("--shard-pools", type=int, default=1,
                    help="shard the paged KV pools' block dim over this many "
                         "devices on a 'kv' mesh axis (DESIGN.md §Sequence-"
                         "sharded pools): each device resides 1/N of pool "
                         "capacity, the block-table walk fetches only the "
                         "blocks a row attends (never a full-pool gather), "
                         "and outputs stay token-identical to replicated "
                         "pools. 1 (default) = replicated")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prompt tokens prefillable per PREFILLING slot per "
                         "engine step (chunked prefill, interleaved with "
                         "decode). Default: 2*block_size for pure-attention "
                         "archs, 0 (whole-prompt) otherwise; pass 0 to "
                         "force whole-prompt prefill")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="flattened tokens per engine step for the unified "
                         "mixed-batch program (several slots' prefill "
                         "chunks + all decode tokens in ONE dispatch). "
                         "Default: prefill_chunk + slots on chunk-capable "
                         "archs; pass 0 to force the split chunk-then-"
                         "decode scheduler (two dispatches per step)")
    ap.add_argument("--prefix-cache", type=int, default=0, choices=[0, 1],
                    help="share KV blocks across requests with a common "
                         "prompt prefix (refcounted blocks + hash-chain "
                         "index; requires chunked prefill). 0 (default) is "
                         "bit-identical to the engine without the cache")
    ap.add_argument("--stagger", type=float, default=0.0,
                    help="inter-arrival gap in seconds (simulated traffic)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request total-latency deadline in ms (0 = "
                         "none): a request still running past its deadline "
                         "is cancelled mid-decode (blocks released, partial "
                         "output kept) and recorded as timed_out")
    ap.add_argument("--ttft-deadline-ms", type=float, default=0.0,
                    help="per-request TTFT deadline in ms (0 = none): a "
                         "request that has not produced its first token by "
                         "the deadline is dropped as timed_out")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded admission: arrived-but-unadmitted requests "
                         "beyond this are rejected (outcome 'rejected') "
                         "instead of queueing unboundedly")
    ap.add_argument("--fault-plan", default="",
                    help="deterministic fault schedule, e.g. "
                         "'exhaust@6x4;corrupt@9;die@12' (serving/faults.py "
                         "grammar); wraps the run in an EngineSupervisor "
                         "that recovers and replays unfinished requests")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the synthetic workload and fault plan")
    ap.add_argument("--audit", action="store_true",
                    help="statically audit the engine's compiled programs "
                         "before serving (repro.staticcheck: compressed-wire "
                         "contract, dtype drift, host transfers; DESIGN.md "
                         "§Static analysis) and fail fast on any violation")
    args = ap.parse_args()
    enable_compile_cache(Path(__file__).resolve().parents[3])

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    model = Model(cfg)

    policy = NO_COMPRESSION if args.policy == "none" else CompressionPolicy(
        spec=MXSpec.make("fp4_e2m1", 32, "e8m0"), variant=args.variant,
        min_prefill_fraction=args.min_prefill_fraction,
        overlap_chunks=args.overlap_chunks)
    n_dev = len(jax.devices())
    if args.shard_pools > 1:
        mesh = make_kv_mesh(kv=args.shard_pools)
        ctx = make_context(mesh, None, policy=policy, kv_axis="kv")
    else:
        mesh = make_host_mesh() if n_dev > 1 else None
        ctx = make_context(mesh, None, policy=policy)
    print(f"devices={n_dev} policy={policy.describe()}"
          + (f" kv_shards={ctx.kv_shards}" if ctx.kv_sharded else ""))

    params = init_params_sharded(model, ctx, jax.random.PRNGKey(0))
    max_len = args.prompt_len + args.new_tokens + cfg.n_patches * (
        cfg.frontend == "vision")
    fault_plan = FaultPlan.parse(args.fault_plan, seed=args.seed)
    engine = Engine(model, params, ctx, max_slots=args.slots, max_len=max_len,
                    block_size=args.block_size, cache_spec=args.cache_spec,
                    prefill_chunk=args.prefill_chunk,
                    token_budget=args.token_budget,
                    prefix_cache=bool(args.prefix_cache),
                    max_queue=args.max_queue,
                    deadline_s=args.deadline_ms / 1e3 or None,
                    deadline_ttft_s=args.ttft_deadline_ms / 1e3 or None,
                    fault_plan=fault_plan if len(fault_plan) else None)
    if len(fault_plan):
        print(f"fault plan: {fault_plan.describe()}")
    step = (f"mixed, {engine.token_budget}-token budget "
            f"({engine.prefill_chunk} tokens/chunk)" if engine.token_budget
            else (f"split, chunked {engine.prefill_chunk} tokens/step"
                  if engine.prefill_chunk else "split, whole-prompt"))
    pool_mb = engine.kv_pool_bytes() / 1e6
    sharded = (f"{pool_mb:.2f} MB pools, "
               f"{engine.kv_pool_bytes(per_device=True) / 1e6:.2f} MB/device "
               f"over {engine.kv_shards} kv shards"
               if engine.kv_shards > 1 else f"{pool_mb:.2f} MB pools")
    print(f"kv cache: {engine.cache_spec.describe()} "
          f"({sharded}); step: {step}"
          f"; prefix cache: {'on' if engine.prefix_cache else 'off'}")

    if args.audit:
        # static program audit BEFORE any request is served: trace (never
        # execute) every compiled program and check the communication
        # contract the run is about to claim numbers for
        from repro.staticcheck import audit_engine

        report = audit_engine(engine, label=f"{args.arch} serve",
                              prompt_len=args.prompt_len)
        print(report.format_table())
        if not report.ok:
            raise SystemExit("static audit FAILED — not serving")

    n_req = args.requests or args.slots
    rng = np.random.default_rng(args.seed)
    # with the prefix cache on, give the workload something to share: every
    # request opens with the same "system prompt" half (the common serving
    # shape the cache exists for), followed by a per-request suffix
    shared = rng.integers(0, cfg.vocab_size, args.prompt_len // 2).astype(
        np.int32) if args.prefix_cache else np.zeros((0,), np.int32)
    reqs = [
        Request(
            prompt=np.concatenate([shared, rng.integers(
                0, cfg.vocab_size, args.prompt_len - len(shared)
            ).astype(np.int32)]),
            max_new_tokens=args.new_tokens,
            temperature=args.temperature,
            arrival_s=i * args.stagger,
        )
        for i in range(n_req)
    ]
    extra = {}
    if cfg.frontend == "vision":
        extra["patch_embeds"] = patch_embed_stub(cfg, n_req, jax.random.PRNGKey(1))
    if cfg.encoder_decoder:
        extra["encoder_frames"] = audio_frames_stub(cfg, n_req, jax.random.PRNGKey(2))
    # warm up the prefill bucket + decode jits so the reported TTFT/latency
    # measure serving, not XLA compilation — with the fault plan disarmed so
    # warmup steps don't consume (or trip) the measured run's fault events
    plan, engine.fault_plan = engine.fault_plan, None
    engine.run([Request(prompt=reqs[0].prompt.copy(), max_new_tokens=2)],
               extra_inputs={k: v[:1] for k, v in extra.items()} or None)
    engine.fault_plan = plan
    t0 = time.time()
    if len(fault_plan):
        # supervised run: recoverable faults (engine death, corruption,
        # stuck steps) restart the engine and replay unfinished requests
        sup = EngineSupervisor(engine)
        out = sup.run(reqs, extra_inputs=extra or None)
        stats_src = sup.stats
    else:
        out = engine.run(reqs, extra_inputs=extra or None)
        stats_src = engine.stats
    wall = time.time() - t0
    s = stats_src.summary()
    print(f"{s['n_requests']} requests, {s['n_generated']} tokens in "
          f"{wall:.2f}s wall (incl compile); steady tokens/s={s['tokens_per_s']:.1f}")
    print(f"dispatch: {s['n_steps']} steps, {s['n_dispatches']} program "
          f"dispatches, {s['tokens_per_step_mean']:.1f} tokens/step "
          f"({s['prefill_tokens']} prefill + {s['decode_tokens']} decode)")
    if stats_src.read_tiles_held:
        print(f"history read: {s['read_tile_share']:.3f} of the mixed "
              f"step's read tiles hold a query")
    if s["n_steps"]:
        # the engine's step-phase spans (docs/serving.md "Observability"):
        # host time a step outside the device wait is
        # admit+schedule+dispatch+emit
        print("host ms/step: " + ", ".join(
            f"{p} {stats_src.phase_s.get('engine.' + p, 0.0) / s['n_steps'] * 1e3:.2f}"
            for p in ("admit", "schedule", "dispatch", "wait", "emit")))
    if "compressed" in engine.gate_variants():
        print(f"compression gate: {s['n_compressed_steps']} compressed / "
              f"{s['n_steps'] - s['n_compressed_steps']} dense steps")
    if engine.prefix_cache:
        print(f"prefix cache: {s['prefill_tokens_skipped']} prompt tokens "
              f"skipped (hit rate {s['prefix_hit_rate']:.2f})")
    print(f"TTFT p50 {s['ttft_p50_s']*1e3:.1f} ms, p90 {s['ttft_p90_s']*1e3:.1f} ms; "
          f"TPOT p50 {s['tpot_p50_s']*1e3:.2f} ms, p95 {s['tpot_p95_s']*1e3:.2f} ms; "
          f"latency p50 {s['latency_p50_s']*1e3:.1f} ms; "
          f"preemptions={s['n_preemptions']}")
    print(f"outcomes: {s['n_ok']} ok, {s['n_rejected']} rejected, "
          f"{s['n_timed_out']} timed out, {s['n_cancelled']} cancelled; "
          f"goodput={s['goodput_tokens_per_s']:.1f} tok/s")
    if len(fault_plan):
        r = sup.report()
        print(f"recoveries: {r['n_recoveries']} "
              f"({r['n_hard']} hard, {r['n_warm']} warm) "
              f"recovery {r['recovery_s_total']*1e3:.1f} ms "
              f"+ backoff {r['backoff_s_total']*1e3:.1f} ms; "
              f"errors={r['errors']}")
    stats = engine.measure_ttft(args.prompt_len, iters=4,
                                extra_inputs=extra or None)
    print(f"prefill TTFT median {stats['median_s']*1e3:.2f} ms "
          f"(std {stats['std_s']*1e3:.2f})")
    print("first request tokens:", out[0].output.tolist())


if __name__ == "__main__":
    main()
