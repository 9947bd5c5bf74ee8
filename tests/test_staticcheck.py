"""Static analysis subsystem (DESIGN.md §Static analysis): the jaxpr
auditor over ``Engine.trace_programs()`` and the AST lint pass.

The mutation tests are the point: each seeds a violation the auditor exists
to catch (a dense all-gather under a compressing policy, an f32 upcast in
the fp4 path, a host callback in a step program, an unhashable static arg)
and asserts the audit turns red — while the green-path tests pin that the
real engine matrix passes clean."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import PAPER_DEFAULT
from repro.core.tp import TPContext
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.serving import Engine
from repro.staticcheck import (
    audit_engine, audit_program, lint_paths, lint_source,
)
from repro.staticcheck.jaxpr_audit import audit_static_args
from tests.conftest import fp32_reduced

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def small_model():
    cfg = fp32_reduced("internlm2-1.8b")
    model = Model(cfg)
    return cfg, model, model.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tp_mesh():
    """1-device TP mesh: real 'model' axis semantics (collectives present in
    the jaxpr) without a multi-device runtime — test_collectives.py idiom."""
    return make_mesh((1,), ("model",))


def _tp_engine(model, params, mesh, **kw):
    ctx = TPContext(mesh=mesh, data_axes=(), policy=PAPER_DEFAULT)
    with jax.set_mesh(mesh):
        return Engine(model, params, ctx, max_slots=2, max_len=64,
                      cache_dtype=jnp.float32, prefill_chunk=8, **kw)


# ------------------------------------------------------------- green matrix


@pytest.mark.parametrize("cache_spec,token_budget", [
    (None, None), (None, 0), ("fp4_e2m1", None), ("fp4_e2m1", 0),
    ("bf16+pallas", None), ("fp4_e2m1+pallas", None),
])
def test_audit_green_on_engine_matrix(small_model, tp_mesh, cache_spec,
                                      token_budget):
    """dense+fp4 x split+mixed all audit clean on a compressing TP ctx, and
    the compressed-expectation lands exactly where the policy says: prefill-
    side programs compressed (budget >= min_tokens), decode not (paper §5.2
    gating strips the policy from the decode ctx)."""
    _, model, params = small_model
    kw = {} if token_budget is None else {"token_budget": token_budget}
    eng = _tp_engine(model, params, tp_mesh, cache_spec=cache_spec, **kw)
    report = audit_engine(eng, prompt_len=16)
    assert report.ok, report.failures()
    by_name = {p.name: p for p in report.programs}
    assert not by_name["decode"].compressed_expected
    step = "mixed" if token_budget is None else "chunk"
    assert by_name[step].compressed_expected
    # compressed wire = uint8 only; dense decode psum stays float
    assert all(r.dtype == "uint8" for r in by_name[step].collectives)
    assert by_name[step].collectives, "compressed step lost its collectives"
    assert any(r.dtype == "float32" for r in by_name["decode"].collectives)


def test_trace_programs_surface(small_model, tp_mesh):
    """trace_programs covers exactly the programs the engine dispatches,
    carries boundary avals, and never executes anything on device."""
    _, model, params = small_model
    eng = _tp_engine(model, params, tp_mesh, cache_spec="fp4_e2m1",
                     prefix_cache=True)
    traces = eng.trace_programs()
    # a compressing policy compiles two gate variants; both are traced, and
    # only the compressed one carries the prefill-dominated expectation
    assert set(traces) == {"decode", "mixed", "mixed-dense", "cow"}
    assert traces["mixed"].n_tokens == eng.token_budget
    assert traces["mixed"].prefill_dominated
    assert not traces["mixed-dense"].prefill_dominated
    assert traces["decode"].n_tokens == eng.n_slots
    # with an explicit prompt_len the whole-prompt pair appears too
    traces = eng.trace_programs(prompt_len=16)
    assert set(traces) == {"decode", "mixed", "mixed-dense", "cow",
                           "prefill", "insert"}
    # whole-prompt engines trace their serving pair by default
    whole = Engine(model, params, TPContext(mesh=None), max_slots=2,
                   max_len=64, cache_dtype=jnp.float32, prefill_chunk=0)
    assert set(whole.trace_programs()) == {"decode", "prefill", "insert"}


def test_audit_whole_prompt_hybrid_engine():
    """The whole-prompt prefill/insert pair (recurrent-layer archs) traces
    and audits clean — per-length programs, recurrent state threading."""
    cfg = fp32_reduced("jamba-v0.1-52b")
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = Engine(model, params, TPContext(mesh=None), max_slots=2, max_len=48,
                 cache_dtype=jnp.float32)
    report = audit_engine(eng)
    assert report.ok, report.failures()
    assert {p.name for p in report.programs} == {"decode", "prefill", "insert"}


def test_collective_inventory_matches_wire_math(small_model, tp_mesh):
    """The audited byte counts are the paper's wire accounting: payload
    lastdim = F * elem.bits / 8 bytes and one scale byte per block, per
    ``wire_arrays_shape``."""
    cfg, model, params = small_model
    eng = _tp_engine(model, params, tp_mesh, cache_spec="fp4_e2m1")
    report = audit_engine(eng)
    mixed = {p.name: p for p in report.programs}["mixed"]
    spec = PAPER_DEFAULT.spec
    payloads = mixed.collectives[0::2]
    scales = mixed.collectives[1::2]
    assert payloads and len(payloads) == len(scales)
    for p, s in zip(payloads, scales):
        assert (p.dtype, s.dtype) == ("uint8", "uint8")
        f = s.shape[-1] * spec.block_size          # dense feature dim
        assert p.shape[-1] == f * spec.elem.bits // 8
        assert p.shape[:-1] == s.shape[:-1] == (1, eng.token_budget)
        assert p.bytes_per_device == np.prod(p.shape)


# ------------------------------------------------------------ mutation tests


def test_dense_collective_under_compressing_policy_is_red(
        small_model, tp_mesh, monkeypatch):
    """THE failure mode this subsystem exists for: a dense collective
    silently replacing the compressed one in a program whose policy says
    the boundary is compressed."""
    import repro.core.tp as tp_mod

    _, model, params = small_model
    eng = _tp_engine(model, params, tp_mesh, cache_spec="fp4_e2m1")
    monkeypatch.setattr(
        tp_mod, "psum_maybe_compressed",
        lambda partial, axis_name, policy, **kw: jax.lax.psum(partial,
                                                              axis_name))
    report = audit_engine(eng)
    assert not report.ok
    fails = report.failures()
    assert any(f.rule == "dense-collective" and f.program == "mixed"
               for f in fails), fails
    # decode is OUTSIDE the compressed contract: no finding there
    assert not any(f.program == "decode" for f in fails)


def test_missing_compression_in_prefill_dominated_program_is_red(
        small_model, tp_mesh):
    """The inverse rule (DESIGN.md §Static auditor): the thesis must be
    PRESENT, not merely not-violated. A prefill-dominated mixed program with
    TP collectives but zero uint8 wire traffic under an active policy turns
    the audit red. The engine's own dense gate variant supplies a real
    all-dense trace: under its own labeling (not prefill-dominated, policy
    stripped) it is green; relabeled as the prefill-dominated program of an
    active policy it must fail."""
    _, model, params = small_model
    eng = _tp_engine(model, params, tp_mesh, cache_spec="fp4_e2m1")
    traces = eng.trace_programs()
    dense = traces["mixed-dense"]
    assert audit_program(dense).ok
    mutant = dataclasses.replace(dense, policy=PAPER_DEFAULT,
                                 prefill_dominated=True)
    rep = audit_program(mutant)
    assert not rep.ok
    assert any(f.rule == "missing-compression" for f in rep.findings), \
        rep.findings
    # the compressed variant satisfies the presence rule by construction
    assert audit_program(traces["mixed"]).ok


def test_f32_upcast_in_fp4_path_is_red(monkeypatch):
    """Silent fp32 upcast inside the fp4 decode/mixed path: force the pool
    dequantizer to emit f32 and the drift escapes to the logits boundary of
    a bf16 engine — the auditor must flag it."""
    import repro.core.mx as mx_mod

    cfg = dataclasses.replace(fp32_reduced("internlm2-1.8b"),
                              dtype="bfloat16")
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = Engine(model, params, TPContext(mesh=None), max_slots=2, max_len=64,
                 cache_spec="fp4_e2m1", prefill_chunk=8)
    assert audit_engine(eng).ok  # green before the mutation

    orig = mx_mod.dequantize
    monkeypatch.setattr(
        mx_mod, "dequantize",
        lambda comp, spec, out_dtype=jnp.float32:
            orig(comp, spec, out_dtype=jnp.float32))
    report = audit_engine(eng)
    assert not report.ok
    assert any(f.rule == "dtype-drift" and f.program == "mixed"
               and "float32" in f.message for f in report.failures()), \
        report.failures()


def test_host_callback_in_step_program_is_red(small_model, monkeypatch):
    """A hidden host round-trip inside a per-step program is an audit
    failure (and is allowed in off-step programs)."""
    _, model, params = small_model
    eng = Engine(model, params, TPContext(mesh=None), max_slots=2, max_len=64,
                 cache_dtype=jnp.float32, prefill_chunk=8)
    orig = model.mixed_step

    def noisy(*args, **kw):
        jax.debug.print("step {}", args[2][0, 0])
        return orig(*args, **kw)

    monkeypatch.setattr(model, "mixed_step", noisy)
    report = audit_engine(eng)
    assert any(f.rule == "host-transfer" and f.program == "mixed"
               for f in report.failures()), report.failures()


def test_audit_recurses_into_pallas_call(tp_mesh):
    """Satellite regression: a collective hidden INSIDE a pallas_call kernel
    body is still inventoried — the kernel jaxpr rides in ``eqn.params`` and
    ``_sub_jaxprs`` recurses into it like any other call primitive. Without
    that recursion a dense TP collective could hide from the audit inside a
    kernel."""
    from jax.experimental import pallas as pl
    from jax.sharding import PartitionSpec as P

    from repro.staticcheck.jaxpr_audit import collect_collectives

    def kernel(x_ref, o_ref):
        o_ref[...] = jax.lax.psum(x_ref[...], "model")

    def prog(x):
        return jax.shard_map(
            lambda xs: pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct((4, 4), jnp.float32),
                interpret=True)(xs),
            mesh=tp_mesh, in_specs=P(), out_specs=P(), check_vma=False)(x)

    jaxpr = jax.make_jaxpr(prog)(jnp.zeros((4, 4), jnp.float32))
    recs = collect_collectives(jaxpr, {"model": 1})
    assert any(r.primitive == "psum" and "model" in r.axes
               for r in recs), recs


def test_pool_gather_on_kernel_engine_is_red(small_model):
    """Mutation for the pool-gather rule: flag a jnp-read engine's step
    traces as kernel_read_path and the full-capacity pool[tables] gathers
    they legitimately contain must turn the audit red — while the genuine
    +pallas engine stays green under the same rule."""
    _, model, params = small_model

    def engine(spec):
        return Engine(model, params, TPContext(mesh=None), max_slots=2,
                      max_len=64, cache_dtype=jnp.float32, cache_spec=spec,
                      prefill_chunk=8)

    for name, trace in engine("fp4_e2m1+pallas").trace_programs().items():
        rep = audit_program(trace)
        assert not any(f.rule == "pool-gather" for f in rep.findings), (
            name, rep.findings)

    jnp_traces = engine("fp4_e2m1").trace_programs()
    red = {}
    for name, trace in jnp_traces.items():
        trace.kernel_read_path = True                  # the mutation
        red[name] = [f for f in audit_program(trace).findings
                     if f.rule == "pool-gather"]
    assert red["mixed"] and red["decode"], red
    # off-step programs (insert/COW) are outside the rule's scope
    traces = engine("fp4_e2m1").trace_programs(prompt_len=16)
    t = traces["insert"]
    t.kernel_read_path = True
    assert not any(f.rule == "pool-gather"
                   for f in audit_program(t).findings)


def test_pool_reshard_mutation_is_red(small_model):
    """Mutation for the pool-reshard rule's gather signature: flag a
    REPLICATED jnp engine's step traces as kv-sharded and the full-capacity
    ``pool[tables]`` gathers they legitimately contain must turn the audit
    red — on a ``kv_shards > 1`` engine a replicated-pool read can only
    exist if the sharding was undone upstream. Unmutated traces (kv_shards
    == 1) and off-step programs stay green."""
    _, model, params = small_model

    def engine():
        return Engine(model, params, TPContext(mesh=None), max_slots=2,
                      max_len=64, cache_dtype=jnp.float32,
                      cache_spec="fp4_e2m1", prefill_chunk=8)

    for name, trace in engine().trace_programs().items():
        assert not any(f.rule == "pool-reshard"
                       for f in audit_program(trace).findings), name

    red = {}
    for name, trace in engine().trace_programs().items():
        trace.kv_shards, trace.kv_axis = 2, "kv"       # the mutation
        red[name] = [f for f in audit_program(trace).findings
                     if f.rule == "pool-reshard"]
    assert red["mixed"] and red["decode"], red
    # off-step programs (insert/COW block moves) are outside the rule
    traces = engine().trace_programs(prompt_len=16)
    t = traces["insert"]
    t.kv_shards, t.kv_axis = 2, "kv"
    assert not any(f.rule == "pool-reshard"
                   for f in audit_program(t).findings)


def test_pool_reshard_allgather_is_red():
    """The rule's other signature: an ``all_gather`` over the kv axis whose
    operand leads with a pool slab's (blocks, block_size) head is
    full-capacity replication on the wire — red even handcrafted on a
    1-device 'kv' mesh (the slab-head set includes the full-capacity head
    precisely so a size-1 axis trace still matches). The legit masked-psum
    exchange moves TABLE-sized operands and stays green."""
    from jax.sharding import PartitionSpec as P

    from repro.staticcheck.report import ProgramTrace

    kv_mesh = make_mesh((1,), ("kv",))
    pool = jnp.zeros((8, 16, 4), jnp.float32)

    def reshard_findings(body):
        fn = lambda p: jax.shard_map(body, mesh=kv_mesh, in_specs=P(),
                                     out_specs=P(), check_vma=False)(p)
        trace = ProgramTrace(
            name="decode", jaxpr=jax.make_jaxpr(fn)(pool), policy=None,
            n_tokens=1, compute_dtype="float32", is_step=True,
            axis_sizes={"kv": 1}, pool_avals=(((8, 16, 4), "float32"),),
            kv_shards=2, kv_axis="kv")
        return [f for f in audit_program(trace).findings
                if f.rule == "pool-reshard"]

    red = reshard_findings(lambda p: jax.lax.all_gather(p, "kv", tiled=True))
    assert red, "full-pool all_gather over the kv axis must be red"
    # masked-psum exchange over a table-sized slice: never capacity-shaped
    assert not reshard_findings(lambda p: jax.lax.psum(p[:3], "kv"))


def test_state_dtype_drift_is_red(small_model):
    """A program whose output state avals differ from its input state avals
    (pool storage format change mid-flight) is flagged."""
    _, model, params = small_model
    eng = Engine(model, params, TPContext(mesh=None), max_slots=2, max_len=64,
                 cache_dtype=jnp.float32, prefill_chunk=8)
    traces = eng.trace_programs()
    t = traces["mixed"]
    t.state_out = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16), t.state_out)
    rep = audit_program(t)
    assert any(f.rule == "dtype-drift" for f in rep.findings), rep.findings


# --------------------------------------------------------------- lint rules


def test_lint_mutable_default_arg():
    src = "def f(x, ys=[], zs={}):\n    return x\n"
    rules = {v.rule for v in lint_source(src)}
    assert "SC001" in rules


def test_lint_device_op_in_host_scheduler():
    src = textwrap.dedent("""
        import jax.numpy as jnp
        class BlockAllocator:
            def alloc(self, n):
                return jnp.arange(n)
    """)
    vs = lint_source(src, path="src/repro/serving/kv_cache.py")
    assert any(v.rule == "SC002" for v in vs), vs
    # same code outside a host zone is fine
    assert not any(v.rule == "SC002"
                   for v in lint_source(src, path="src/repro/core/x.py"))


def test_lint_allocator_state_encapsulation():
    src = textwrap.dedent("""
        class Engine:
            def grab(self, allocator):
                return allocator._free.popleft()
    """)
    vs = lint_source(src, path="src/repro/serving/engine.py")
    assert any(v.rule == "SC003" for v in vs), vs
    inside = textwrap.dedent("""
        class BlockAllocator:
            def alloc(self):
                return self._free.popleft()
    """)
    assert not any(v.rule == "SC003" for v in lint_source(
        inside, path="src/repro/serving/kv_cache.py"))


def test_lint_unhashable_static_arg_is_red():
    """Acceptance mutation: an unhashable value at a static_argnames call
    site turns the audit red."""
    src = textwrap.dedent("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("spec",))
        def f(x, spec):
            return x

        def caller(x):
            return f(x, spec=[1, 2])
    """)
    vs = lint_source(src, path="src/repro/kernels/x.py")
    assert any(v.rule == "SC004" and "unhashable" in v.message for v in vs), vs
    # a wrong static name is also red
    bad_name = src.replace('("spec",)', '("speck",)')
    vs = lint_source(bad_name, path="src/repro/kernels/x.py")
    assert any(v.rule == "SC004" and "not a parameter" in v.message
               for v in vs), vs
    # hashable call sites stay green
    ok = src.replace("spec=[1, 2]", "spec=(1, 2)")
    assert not any(v.rule == "SC004"
                   for v in lint_source(ok, path="src/repro/kernels/x.py"))


def test_lint_sync_outside_timing_code():
    src = textwrap.dedent("""
        def serve(x):
            return x.block_until_ready()

        def measure_latency(x):
            return x.block_until_ready()
    """)
    vs = [v for v in lint_source(src, path="src/repro/serving/x.py")
          if v.rule == "SC005"]
    assert len(vs) == 1 and "serve" in vs[0].message, vs
    # benchmarks/tests/scripts are timing code
    assert not any(v.rule == "SC005" for v in lint_source(
        src, path="benchmarks/x.py"))


def test_lint_dead_import():
    src = "import os\nimport sys\n\nprint(sys.argv)\n"
    vs = lint_source(src, path="src/repro/x.py")
    assert any(v.rule == "SC006" and "'os'" in v.message for v in vs)
    # __all__ re-exports count as used
    src2 = "from x import thing\n__all__ = [\"thing\"]\n"
    assert not any(v.rule == "SC006"
                   for v in lint_source(src2, path="src/repro/x.py"))


def test_repo_lints_green():
    """Satellite: the linter lands green on the repo — no baseline file."""
    vs = lint_paths([os.path.join(REPO, "src", "repro"),
                     os.path.join(REPO, "scripts")])
    assert not vs, "\n".join(str(v) for v in vs)


def test_repo_static_args_green():
    assert not audit_static_args([os.path.join(REPO, "src", "repro")])


# ------------------------------------------------------- TP-mesh subprocess


def test_audit_on_multidevice_tp_mesh():
    """The acceptance TP-mesh case: audit a real data(2) x model(4) engine in
    a subprocess with 8 forced host devices — compressed uint8 traffic with
    axis_size 4, green across the board."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs import get_config, reduced_config
        from repro.core.policy import PAPER_DEFAULT
        from repro.launch.mesh import make_mesh
        from repro.launch.sharding import make_context
        from repro.models.model import Model
        from repro.serving import Engine
        from repro.staticcheck import audit_engine

        cfg = dataclasses.replace(reduced_config(get_config("internlm2-1.8b")),
                                  dtype="float32")
        model = Model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = make_context(mesh, None, policy=PAPER_DEFAULT)
        with jax.set_mesh(mesh):
            eng = Engine(model, params, ctx, max_slots=2, max_len=64,
                         cache_dtype=jnp.float32, cache_spec="fp4_e2m1",
                         prefill_chunk=8)
        rep = audit_engine(eng, prompt_len=16)
        assert rep.ok, rep.failures()
        mixed = {p.name: p for p in rep.programs}["mixed"]
        assert mixed.compressed_expected
        assert mixed.collectives, "no TP collectives on a TP mesh"
        assert all(r.dtype == "uint8" for r in mixed.collectives)
        assert all(r.axis_size == 4 for r in mixed.collectives)
        print("TP-MESH-AUDIT-OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, env=env, cwd=REPO)
    assert proc.returncode == 0, \
        f"STDOUT:{proc.stdout}\nSTDERR:{proc.stderr[-3000:]}"
    assert "TP-MESH-AUDIT-OK" in proc.stdout
