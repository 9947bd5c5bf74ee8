"""The engine's own spans and counters (``ServeStats.span``) and the named
device scopes and kernel names a profiler trace credits time to
(docs/serving.md "Observability")."""
import glob
import json
import os
import re
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import MXSpec
from repro.core.tp import TPContext
from repro.models.model import Model
from repro.serving import Engine, Request, ServeStats
from repro.serving.errors import OUTCOME_OK
from tests.conftest import fp32_reduced

REPO = Path(__file__).resolve().parent.parent
PHASES = ("engine.schedule", "engine.dispatch", "engine.wait", "engine.emit")


@pytest.fixture(scope="module")
def engine():
    cfg = fp32_reduced("internlm2-1.8b")
    model = Model(cfg)
    return Engine(model, model.init_params(jax.random.PRNGKey(0)),
                  TPContext(mesh=None), max_slots=2, max_len=64,
                  prefill_chunk=16)


def _requests(vocab, n=3):
    return [Request(prompt=(np.arange(5 + 7 * i, dtype=np.int32) * 3)
                    % vocab, max_new_tokens=3, arrival_s=0.01 * i)
            for i in range(n)]


def test_mixed_run_spans_each_phase_once_a_step(engine):
    engine.run(_requests(engine.cfg.vocab_size))
    st = engine.stats
    assert st.n_steps > 0
    assert st.phase_n["engine.step"] == st.n_steps
    for p in PHASES:
        assert st.phase_n[p] == st.n_steps, p
        assert st.phase_s[p] > 0, p
    # the phases are disjoint children of the step
    assert sum(st.phase_s[p] for p in PHASES) <= st.phase_s["engine.step"]
    # admission runs once a loop pass, before the step it feeds
    assert st.phase_n["engine.admit"] >= st.n_steps


def test_split_scheduler_spans_admit_and_step_only():
    cfg = fp32_reduced("internlm2-1.8b")
    model = Model(cfg)
    eng = Engine(model, model.init_params(jax.random.PRNGKey(0)),
                 TPContext(mesh=None), max_slots=2, max_len=64,
                 prefill_chunk=16, token_budget=0)
    eng.run(_requests(cfg.vocab_size, n=2))
    st = eng.stats
    assert st.phase_n["engine.step"] == st.n_steps > 0
    assert st.phase_n["engine.admit"] >= st.n_steps
    assert not set(PHASES) & set(st.phase_n)


def test_watchdog_times_the_step_not_the_admission():
    """A slow whole-prompt admission (its prefill, a new bucket's compile)
    while another request runs is no stuck step: ``step_timeout_s`` reads
    the ``engine.step`` span, which opens after admission."""
    cfg = fp32_reduced("internlm2-1.8b")
    model = Model(cfg)
    eng = Engine(model, model.init_params(jax.random.PRNGKey(0)),
                 TPContext(mesh=None), max_slots=2, max_len=64,
                 prefill_chunk=0)

    def requests():
        # the second arrives while the first's 40 tokens are decoding
        return [Request(prompt=np.arange(8, dtype=np.int32) + 3 * i,
                        max_new_tokens=40 if i == 0 else 3,
                        arrival_s=0.005 * i) for i in range(2)]

    eng.run(requests())  # compiles every program outside the watchdog
    eng.step_timeout_s = 1.0
    slow, admit = [], eng._admit

    def slow_admit(w, slot, ids):
        if eng._running:
            slow.append(slot)
            time.sleep(1.5)
        return admit(w, slot, ids)

    eng._admit = slow_admit
    reqs = eng.run(requests())
    assert slow  # a request was admitted with another one running
    assert all(r.outcome == OUTCOME_OK for r in reqs)
    assert eng.stats.phase_s["engine.admit"] >= 1.5


def test_merge_sums_phases():
    a, b = ServeStats(), ServeStats()
    with a.span("engine.step", step_num=1):
        with a.span("engine.schedule"):
            pass
    with b.span("engine.schedule"):
        pass
    with b.span("engine.idle"):
        pass
    want = {k: a.phase_s.get(k, 0.0) + b.phase_s.get(k, 0.0)
            for k in set(a.phase_s) | set(b.phase_s)}
    a.merge(b)
    assert a.phase_n == {"engine.step": 1, "engine.schedule": 2,
                         "engine.idle": 1}
    assert a.phase_s == pytest.approx(want)


def test_span_records_on_an_exception():
    st = ServeStats()
    with pytest.raises(KeyError):
        with st.span("engine.dispatch") as sp:
            raise KeyError("x")
    assert st.phase_n == {"engine.dispatch": 1}
    assert st.phase_s["engine.dispatch"] == sp.seconds >= 0


def test_profiler_trace_holds_engine_spans(engine, tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.run(_requests(engine.cfg.vocab_size))
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    steps, names = set(), set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    names.add(ev.name)
                    if ev.name == "engine.step":
                        steps.add(dict(ev.stats)["step_num"])
    assert {"engine.admit", "engine.step", *PHASES} <= names
    # the step number is the key the trace shares with ServeStats
    assert steps == set(range(1, engine.stats.n_steps + 1))


def _op_names(hlo: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo))


@pytest.mark.parametrize("cache_spec", ["bf16", "bf16+pallas"])
def test_mixed_step_program_names_paged_attention(cache_spec):
    cfg = fp32_reduced("internlm2-1.8b")
    model = Model(cfg)
    eng = Engine(model, model.init_params(jax.random.PRNGKey(0)),
                 TPContext(mesh=None), max_slots=2, max_len=64,
                 prefill_chunk=16, cache_spec=cache_spec,
                 cache_dtype=jnp.bfloat16)
    names = _op_names(eng.step_program_text())
    scoped = [n for n in names if "/paged_attention/" in n]
    assert scoped
    # the projections around it stay outside the scope
    assert any("dot_general" in n for n in names
               if "/paged_attention/" not in n)
    assert not any("tp_codec" in n for n in names)


TP_SCRIPT = textwrap.dedent("""
    import json, re, sys
    sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
    import jax
    from repro.configs import get_config, reduced_config
    from repro.core.formats import MXSpec
    from repro.core.policy import CompressionPolicy
    from repro.launch.mesh import make_host_mesh
    from repro.launch.sharding import make_context
    from repro.models.model import Model
    from repro.serving import Engine

    cfg = reduced_config(get_config("qwen2-7b"))
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    mesh = make_host_mesh(data=1, model=4)
    out = {}
    for use_pallas in (False, True):
        pol = CompressionPolicy(spec=MXSpec.make("fp4_e2m1", 32, "e8m0"),
                                variant="gather", use_pallas=use_pallas)
        eng = Engine(model, params, make_context(mesh, None, policy=pol),
                     max_slots=2, max_len=64, prefill_chunk=16)
        out[str(use_pallas)] = {
            k: sorted(set(re.findall(r'op_name="([^"]*)"',
                                     eng.step_program_text(compressed=c))))
            for k, c in (("compressed", True), ("dense", False))}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def tp_op_names(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", TP_SCRIPT, str(REPO)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("use_pallas", [False, True])
def test_tp_codec_scope_only_in_the_compressed_variant(tp_op_names,
                                                       use_pallas):
    got = tp_op_names[str(use_pallas)]
    codec = [n for n in got["compressed"] if "/tp_codec/" in n]
    assert codec
    assert not any("tp_codec" in n for n in got["dense"])
    # the scopes never nest, so their shares of a trace add up
    for variant in got.values():
        assert not any("paged_attention" in n and "tp_codec" in n
                       for n in variant)
        assert any("/paged_attention/" in n for n in variant)


def _kernel_jaxprs():
    from repro.core.mx import MXCompressed
    from repro.kernels import ops
    from repro.kernels.paged_attention import paged_attention

    spec = MXSpec.make("fp4_e2m1", 32, "e8m0")
    x = jnp.ones((8, 64), jnp.float32)
    comp = ops.mx_quantize(x, spec)
    stacked = MXCompressed(jnp.stack([comp.payload] * 2),
                           jnp.stack([comp.scales] * 2))
    pool = jnp.zeros((4, 16, 2 * 32), jnp.bfloat16)
    return {
        "mx_quantize": lambda: jax.make_jaxpr(
            lambda a: ops.mx_quantize(a, spec))(x),
        "mx_dequantize": lambda: jax.make_jaxpr(
            lambda c: ops.mx_dequantize(c, spec))(comp),
        "mx_dequantize_reduce": lambda: jax.make_jaxpr(
            lambda c: ops.mx_dequant_reduce(c, spec))(stacked),
        "paged_attention": lambda: jax.make_jaxpr(
            lambda q: paged_attention(
                q, pool, pool, jnp.zeros((2, 4), jnp.int32),
                jnp.zeros((2,), jnp.int32), jnp.zeros((2, 1), jnp.int32),
                kv_heads=2, scale=0.1, interpret=True))(
            jnp.ones((2, 1, 4 * 32), jnp.bfloat16)),
    }


def _pallas_names(jaxpr) -> list:
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(str(eqn.params["name"]))
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                names += _pallas_names(getattr(inner, "jaxpr", inner))
    return names


@pytest.mark.parametrize("kernel", ["mx_quantize", "mx_dequantize",
                                    "mx_dequantize_reduce",
                                    "paged_attention"])
def test_every_pallas_call_is_named(kernel):
    assert _pallas_names(_kernel_jaxprs()[kernel]().jaxpr) == [kernel]
