"""Serving engine + data pipeline behaviour."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tp import TPContext
from repro.data import Batches, ByteTokenizer, corpus_tokens
from repro.models.frontends import audio_frames_stub, patch_embed_stub
from repro.models.model import Model
from repro.serving import Engine, Request, cache_bytes
from tests.conftest import fp32_reduced

CTX = TPContext(mesh=None)


def test_engine_batched_requests():
    cfg = fp32_reduced("internlm2-1.8b")
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = Engine(model, params, CTX, batch_size=4, max_len=64)
    reqs = [Request(prompt=np.arange(5 + i, dtype=np.int32) % cfg.vocab_size,
                    max_new_tokens=6) for i in range(4)]
    out = engine.run(reqs)
    for r in out:
        assert r.output.shape == (6,)
        assert r.ttft_s > 0 and r.latency_s >= r.ttft_s


def test_engine_greedy_deterministic():
    cfg = fp32_reduced("internlm2-1.8b")
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = Engine(model, params, CTX, batch_size=2, max_len=48)
    reqs = lambda: [Request(prompt=np.arange(8, dtype=np.int32), max_new_tokens=5)
                    for _ in range(2)]
    a = engine.run(reqs())[0].output
    b = engine.run(reqs())[0].output
    np.testing.assert_array_equal(a, b)


def test_engine_vlm_and_audio_frontends():
    for arch in ["pixtral-12b", "whisper-medium"]:
        cfg = fp32_reduced(arch)
        model = Model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        extra = {}
        if cfg.frontend == "vision":
            extra["patch_embeds"] = patch_embed_stub(cfg, 2, jax.random.PRNGKey(1),
                                                     jnp.float32)
        if cfg.encoder_decoder:
            extra["encoder_frames"] = audio_frames_stub(cfg, 2, jax.random.PRNGKey(2),
                                                        jnp.float32)
        engine = Engine(model, params, CTX, batch_size=2,
                        max_len=64 + cfg.n_patches, cache_dtype=jnp.float32)
        reqs = [Request(prompt=np.arange(6, dtype=np.int32), max_new_tokens=3)
                for _ in range(2)]
        out = engine.run(reqs, extra_inputs=extra)
        assert out[0].output.shape == (3,), arch


def test_measure_ttft():
    cfg = fp32_reduced("internlm2-1.8b")
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = Engine(model, params, CTX, batch_size=2, max_len=40)
    stats = engine.measure_ttft(16, iters=3)
    assert stats["median_s"] > 0
    assert stats["iters"] == 2  # warmup iteration dropped


def test_measure_ttft_single_iter_keeps_its_sample():
    """Regression: iters=1 used to drop its only sample via times[1:] and
    return NaN medians."""
    cfg = fp32_reduced("internlm2-1.8b")
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = Engine(model, params, CTX, batch_size=2, max_len=40)
    stats = engine.measure_ttft(16, iters=1)
    assert stats["iters"] == 1
    assert np.isfinite(stats["median_s"]) and stats["median_s"] > 0
    assert np.isfinite(stats["std_s"])


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer()
    s = "def f(x):\n    return x  # ünïcode"
    ids = tok.encode(s)
    assert tok.decode(ids) == s


def test_corpus_and_batches():
    toks = corpus_tokens(50_000)
    assert len(toks) == 50_000
    assert toks.min() >= 0 and toks.max() < 256
    b = Batches(toks, 4, 32, seed=1)
    batch = b.next()
    assert batch["tokens"].shape == (4, 32)
    # next-token alignment
    np.testing.assert_array_equal(np.asarray(batch["tokens"][:, 1:]),
                                  np.asarray(batch["targets"][:, :-1]))


# ----------------------------------------------------- continuous batching


@pytest.fixture(scope="module")
def small_model():
    cfg = fp32_reduced("internlm2-1.8b")
    model = Model(cfg)
    return cfg, model, model.init_params(jax.random.PRNGKey(0))


def test_staggered_arrivals_and_per_request_ttft(small_model):
    """5 requests on 2 slots with staggered arrivals: every request gets its
    own TTFT/latency, admissions honor arrival times, and the batched decode
    step never recompiles as requests join and leave."""
    cfg, model, params = small_model
    eng = Engine(model, params, CTX, max_slots=2, max_len=64,
                 cache_dtype=jnp.float32)
    reqs = [Request(prompt=np.arange(4 + i, dtype=np.int32) % cfg.vocab_size,
                    max_new_tokens=3 + i, arrival_s=0.003 * i)
            for i in range(5)]
    out = eng.run(reqs)
    for r in out:
        assert r.output is not None and len(r.output) == r.max_new_tokens
        assert r.timing is not None
        assert r.timing.admitted_s >= r.arrival_s
        assert r.ttft_s > 0 and r.latency_s >= r.ttft_s
    s = eng.stats.summary()
    assert s["n_requests"] == 5
    assert s["n_generated"] == sum(3 + i for i in range(5))
    assert s["tokens_per_s"] > 0
    # inter-token latency (TPOT): one gap per token after the first, pooled
    assert s["n_inter_token_samples"] == sum(2 + i for i in range(5))
    assert np.isfinite(s["tpot_p50_s"]) and s["tpot_p50_s"] > 0
    assert s["tpot_p95_s"] >= s["tpot_p50_s"]
    assert eng.decode_cache_size() == 1


def test_parity_with_single_request_path(small_model):
    """Continuous batching must not change what any one request decodes:
    batched outputs equal each request served alone."""
    cfg, model, params = small_model
    prompts = [np.arange(5 + 3 * i, dtype=np.int32) % cfg.vocab_size
               for i in range(3)]
    batched = Engine(model, params, CTX, max_slots=3, max_len=64,
                     cache_dtype=jnp.float32)
    out = batched.run([Request(prompt=p, max_new_tokens=6, arrival_s=0.002 * i)
                       for i, p in enumerate(prompts)])
    solo = Engine(model, params, CTX, max_slots=1, max_len=64,
                  cache_dtype=jnp.float32)
    for i, p in enumerate(prompts):
        alone = solo.run([Request(prompt=p, max_new_tokens=6)])[0]
        np.testing.assert_array_equal(out[i].output, alone.output)


def test_first_logits_kept_and_sampled_greedily(small_model):
    """keep_first_logits copies each request's first-token logits row; with
    greedy sampling the first output token is that row's argmax."""
    cfg, model, params = small_model
    eng = Engine(model, params, CTX, max_slots=2, max_len=64,
                 cache_dtype=jnp.float32, keep_first_logits=True)
    out = eng.run([Request(prompt=np.arange(5 + 4 * i, dtype=np.int32),
                           max_new_tokens=3) for i in range(3)])
    for r in out:
        assert r.first_logits.shape == (cfg.vocab_size,)
        assert int(np.argmax(r.first_logits)) == int(r.output[0])
    plain = Engine(model, params, CTX, max_slots=2, max_len=64,
                   cache_dtype=jnp.float32)
    assert plain.run([Request(prompt=np.arange(5, dtype=np.int32),
                              max_new_tokens=2)])[0].first_logits is None


def test_check_finite_stops_on_nonfinite_logits(small_model):
    """check_finite turns the sampling-boundary watch on without a fault
    plan: non-finite logits raise instead of becoming tokens."""
    from repro.serving import WireCorruption

    cfg, model, params = small_model
    bad = jax.tree.map(lambda x: x, params)
    bad["final_norm"]["w"] = bad["final_norm"]["w"].at[0].set(jnp.nan)
    req = lambda: [Request(prompt=np.arange(6, dtype=np.int32),
                           max_new_tokens=2)]
    eng = Engine(model, bad, CTX, max_slots=1, max_len=32,
                 cache_dtype=jnp.float32, check_finite=True)
    with pytest.raises(WireCorruption):
        eng.run(req())
    unchecked = Engine(model, bad, CTX, max_slots=1, max_len=32,
                       cache_dtype=jnp.float32)
    assert len(unchecked.run(req())[0].output) == 2


def test_block_freelist_reuse_after_eviction(small_model):
    """Under a deliberately tiny block pool the scheduler preempts
    (evict-and-recompute); evicted blocks return to the free list, get
    reused, and outputs still match an unconstrained run."""
    cfg, model, params = small_model
    mk = lambda: [Request(prompt=np.arange(20, dtype=np.int32),
                          max_new_tokens=30) for _ in range(2)]
    tiny = Engine(model, params, CTX, max_slots=2, max_len=64, block_size=16,
                  n_blocks=7, cache_dtype=jnp.float32)
    out = tiny.run(mk())
    assert tiny.stats.summary()["n_preemptions"] >= 1
    assert tiny.allocator.n_free == tiny.n_blocks - 1  # all blocks returned
    assert tiny.allocator.high_water <= tiny.n_blocks - 1
    # conservation through the preempt-readmit-finish cycle: every id is
    # back exactly once, none lost, none duplicated, null block never listed
    free_ids = [b for d in tiny.allocator._free for b in d]
    assert sorted(free_ids) == list(range(1, tiny.n_blocks))
    assert tiny.allocator._free_set == set(free_ids)
    big = Engine(model, params, CTX, max_slots=2, max_len=64,
                 cache_dtype=jnp.float32)
    ref = big.run(mk())
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(a.output, b.output)


# ---------------------------------------------------- allocator invariants


def test_allocator_rejects_double_release():
    from repro.serving import BlockAllocator

    a = BlockAllocator(8)
    ids = a.alloc(3)
    a.release(ids[:1])
    with pytest.raises(ValueError, match="double release"):
        a.release(ids[:1])
    with pytest.raises(ValueError, match="double release"):
        a.release([ids[1], ids[1]])  # duplicate within one call
    # failed releases must not have corrupted state
    a.release(ids[1:])
    assert a.n_free == 7 and a.n_allocated == 0


def test_allocator_rejects_null_and_out_of_range():
    from repro.serving import BlockAllocator

    a = BlockAllocator(8)
    ids = a.alloc(2)
    with pytest.raises(ValueError, match="NULL_BLOCK"):
        a.release([0])
    with pytest.raises(ValueError, match="out-of-range"):
        a.release([8])
    with pytest.raises(ValueError, match="out-of-range"):
        a.release([-1])
    a.release(ids)
    assert a.n_free == 7


def test_continuous_engine_hybrid_arch():
    """Recurrent layers (mamba) ride through the paged engine via exact-length
    prefill and slot-batched state."""
    cfg = fp32_reduced("jamba-v0.1-52b")
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = Engine(model, params, CTX, max_slots=2, max_len=48,
                 cache_dtype=jnp.float32)
    reqs = [Request(prompt=np.arange(6, dtype=np.int32), max_new_tokens=3)
            for _ in range(2)]
    out = eng.run(reqs)
    for r in out:
        assert r.output.shape == (3,)
    solo = Engine(model, params, CTX, max_slots=1, max_len=48,
                  cache_dtype=jnp.float32)
    alone = solo.run([Request(prompt=np.arange(6, dtype=np.int32),
                              max_new_tokens=3)])[0]
    np.testing.assert_array_equal(out[0].output, alone.output)


def test_whole_prompt_prefill_fn_cache_is_bounded(small_model):
    """The per-bucket whole-prompt program cache is an LRU with a hard cap
    (hybrid archs compile per exact length — unbounded without this)."""
    cfg, model, params = small_model
    eng = Engine(model, params, CTX, max_slots=2, max_len=64,
                 cache_dtype=jnp.float32, prefill_chunk=0)
    eng.PREFILL_FN_CACHE_MAX = 2
    for n in (5, 20, 40):  # buckets 16, 32, 64
        eng._prefill_for(n)
    assert len(eng._prefill_fns) == 2
    assert 16 not in eng._prefill_fns  # oldest bucket evicted
    eng._prefill_for(20)               # LRU touch keeps 32 resident
    eng._prefill_for(5)
    assert set(eng._prefill_fns) == {16, 32}


def test_cache_bytes_accounting():
    from repro.configs import get_config

    cfg = get_config("gemma3-4b")
    full = cache_bytes(cfg, batch=1, max_len=32768)
    ring = cache_bytes(cfg, batch=1, max_len=32768, ring=True)
    assert ring < full * 0.25  # 29/34 layers shrink to window 1024
