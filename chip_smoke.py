"""Chip smoke run: serve full-width models through the engine on a TPU.

    python chip_smoke.py            # one chip: internlm2-1.8b
    python chip_smoke.py --tp4      # four chips: qwen2-7b, tensor-parallel

Default (one chip): internlm2-1.8b at its published widths with random
weights from ``--seed`` serves 8 requests (prompt 512, 32 new tokens) on a
4-slot ``Engine`` with the mixed token-budget step and 16-token KV blocks,
once per pool mode: ``bf16`` (jnp read path), ``bf16+pallas`` and
``fp4_e2m1+pallas`` (the Pallas paged-attention kernel). It fails unless
every request ends OK with finite logits, the two bf16 modes' first-token
logits agree within rel-L2 2e-2 (both read the same pools; only the order
of the attention sums differs), and each ``+pallas`` step program holds a
``tpu_custom_call``.

``--tp4`` runs only qwen2-7b on a ``model=4`` mesh, parameters initialised
straight into their shards: the same 8 requests with dense collectives and
with the paper's policy (FP4 E2M1, block 32, E8M0, gather variant, Pallas
codec). It fails unless every request ends OK with finite logits, the
compressed step all-gathers uint8 payloads through codec kernels, and its
first-token logits differ from the dense run's by a finite, nonzero rel-L2.

Times printed here are a smoke run's, not benchmark numbers. The last line
of standard output is one JSON object: ``{"ok": true, "device": {...}}``.
Without a TPU it exits non-zero before serving anything. Everything runs in
this one process.
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.formats import MXSpec  # noqa: E402
from repro.core.policy import NO_COMPRESSION, CompressionPolicy  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.sharding import init_params_sharded, make_context  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serving import OUTCOME_OK, Engine, Request  # noqa: E402

N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 8, 512, 32
SLOTS, BLOCK = 4, 16
PALLAS_RTOL = 2e-2


def rel_l2(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def divergence(outs_a, outs_b):
    """Per request, the first output index where two runs' greedy tokens
    differ (None where they agree throughout)."""
    return [next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
            for a, b in zip(outs_a, outs_b)]


def prompts(vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, PROMPT_LEN).astype(np.int32)
            for _ in range(N_REQUESTS)]


def serve(model, params, ctx, prompt_list, label, **engine_kw):
    """Build an engine, warm it up (compiles), serve the requests; returns
    (engine, requests, first-token logits). Raises on any failed check."""
    t0 = time.perf_counter()
    engine = Engine(model, params, ctx, max_slots=SLOTS,
                    max_len=PROMPT_LEN + NEW_TOKENS, block_size=BLOCK,
                    check_finite=True, keep_first_logits=True, **engine_kw)
    engine.run([Request(prompt=prompt_list[0].copy(), max_new_tokens=2)])
    compile_s = time.perf_counter() - t0
    reqs = [Request(prompt=p.copy(), max_new_tokens=NEW_TOKENS)
            for p in prompt_list]
    t0 = time.perf_counter()
    engine.run(reqs)
    wall = time.perf_counter() - t0
    bad = [(i, r.outcome) for i, r in enumerate(reqs)
           if r.outcome != OUTCOME_OK or len(r.output) != NEW_TOKENS]
    if bad:
        raise RuntimeError(f"{label}: requests not OK: {bad}")
    logits = np.stack([r.first_logits for r in reqs])
    if not np.isfinite(logits).all():
        raise RuntimeError(f"{label}: non-finite first-token logits")
    s = engine.stats.summary()
    mem = jax.devices()[0].memory_stats() or {}
    print(f"[{label}] smoke run, not benchmark numbers: compile+warmup "
          f"{compile_s:.1f} s, serve {wall:.2f} s, TTFT p50 "
          f"{s['ttft_p50_s'] * 1e3:.1f} ms, TPOT p50 "
          f"{s['tpot_p50_s'] * 1e3:.2f} ms, {s['n_steps']} steps, peak "
          f"device bytes {mem.get('peak_bytes_in_use', 'n/a')}", flush=True)
    return engine, reqs, logits


def phase_one_chip(seed: int):
    cfg = get_config("internlm2-1.8b")
    model = Model(cfg)
    ctx = make_context(None)
    params = init_params_sharded(model, ctx, jax.random.PRNGKey(seed))
    prompt_list = prompts(cfg.vocab_size, seed)
    runs, failures = {}, []
    for mode in ("bf16", "bf16+pallas", "fp4_e2m1+pallas"):
        try:
            engine, reqs, logits = serve(model, params, ctx, prompt_list,
                                         mode, cache_spec=mode)
            if mode.endswith("+pallas"):
                if "tpu_custom_call" not in engine.step_program_text():
                    raise RuntimeError(f"{mode}: no tpu_custom_call in the "
                                       f"compiled mixed step")
                print(f"[{mode}] mixed step holds tpu_custom_call", flush=True)
            runs[mode] = ([r.output.tolist() for r in reqs], logits)
            del engine
        except Exception:
            traceback.print_exc()
            failures.append(mode)
    if "bf16" in runs and "bf16+pallas" in runs:
        (out_j, lg_j), (out_k, lg_k) = runs["bf16"], runs["bf16+pallas"]
        rel = rel_l2(lg_k, lg_j)
        print(f"bf16+pallas vs bf16: first-token logits rel-L2 {rel:.3e} "
              f"(limit {PALLAS_RTOL}); greedy divergence index per request "
              f"{divergence(out_k, out_j)}", flush=True)
        if not rel <= PALLAS_RTOL:
            failures.append("bf16+pallas parity")
    if "bf16" in runs and "fp4_e2m1+pallas" in runs:
        (out_j, lg_j), (out_q, lg_q) = runs["bf16"], runs["fp4_e2m1+pallas"]
        print(f"fp4_e2m1+pallas vs bf16: first-token logits rel-L2 "
              f"{rel_l2(lg_q, lg_j):.3e}; greedy divergence index per "
              f"request {divergence(out_q, out_j)}", flush=True)
    return failures


def phase_tp4(seed: int):
    if len(jax.devices()) < 4:
        raise SystemExit(f"--tp4 needs 4 devices, found {len(jax.devices())}")
    cfg = get_config("qwen2-7b")
    model = Model(cfg)
    mesh = make_host_mesh(data=1, model=4)
    paper = CompressionPolicy(spec=MXSpec.make("fp4_e2m1", 32, "e8m0"),
                              variant="gather", use_pallas=True)
    ctx_dense = make_context(mesh, None, policy=NO_COMPRESSION)
    ctx_paper = make_context(mesh, None, policy=paper)
    params = init_params_sharded(model, ctx_dense, jax.random.PRNGKey(seed))
    dev0 = jax.devices()[0]
    per_dev = sum(s.data.nbytes for leaf in jax.tree.leaves(params)
                  for s in leaf.addressable_shards if s.device == dev0)
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    print(f"qwen2-7b parameters: {total} bytes, {per_dev} on {dev0} "
          f"({per_dev / total:.3f} of the model)", flush=True)
    prompt_list = prompts(cfg.vocab_size, seed)
    failures = []
    engine, reqs, lg_dense = serve(model, params, ctx_dense, prompt_list,
                                   "tp4 dense")
    out_dense = [r.output.tolist() for r in reqs]
    del engine
    engine, reqs, lg_paper = serve(model, params, ctx_paper, prompt_list,
                                   "tp4 fp4_e2m1 gather pallas")
    print(f"[tp4 compressed] gate: {engine.gate_counts}", flush=True)
    hlo = engine.step_program_text(compressed=True)
    u8_gathers = re.findall(r"= \(?u8\[[^\n]*? all-gather(?:-start)?\(", hlo)
    print(f"[tp4 compressed] uint8 all-gathers in the compiled step: "
          f"{len(u8_gathers)}; tpu_custom_call: {'tpu_custom_call' in hlo}",
          flush=True)
    if not u8_gathers or "tpu_custom_call" not in hlo:
        failures.append("compressed step lacks uint8 gathers or codec kernels")
    if not engine.gate_counts["compressed"]:
        failures.append("no step took the compressed program")
    rel = rel_l2(lg_paper, lg_dense)
    print(f"compressed vs dense: first-token logits rel-L2 {rel:.3e}; greedy "
          f"divergence index per request "
          f"{divergence([r.output.tolist() for r in reqs], out_dense)}",
          flush=True)
    if not (np.isfinite(rel) and rel > 0):
        failures.append("compressed logits rel-L2 not finite and positive")
    return failures


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tp4", action="store_true",
                    help="four chips: qwen2-7b tensor-parallel, dense vs "
                         "the paper's compressed collectives (only)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the random weights and prompts")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform!r}); this "
                 f"script runs only on the chip")
    enable_compile_cache(ROOT)
    failures = phase_tp4(args.seed) if args.tp4 else phase_one_chip(args.seed)
    result = {"ok": not failures,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices())}}
    if failures:
        result["failed"] = failures
    print(json.dumps(result), flush=True)
    sys.exit(0 if not failures else 1)


if __name__ == "__main__":
    main()
