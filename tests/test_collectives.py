"""Multi-device collective tests — run in a subprocess with 8 host devices so
the main pytest process keeps the default single-device view."""
import os
import subprocess
import sys
import textwrap

import pytest

_PREAMBLE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, re, dataclasses
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import set_mesh
from repro.launch.mesh import make_mesh
from repro.core import TPContext, row_linear, fused_mlp, PAPER_DEFAULT, NO_COMPRESSION
from repro.core.policy import CompressionPolicy
from repro.core.formats import MXSpec
mesh = make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(8, 16, 256)), jnp.float32)
w = jnp.asarray(rng.normal(size=(256, 128)) / 16, jnp.float32)
xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
ctx_l = TPContext(mesh=None)
yl = row_linear(ctx_l, x, w)
def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-9))
"""


def run_case(body: str):
    script = _PREAMBLE + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, f"STDOUT:{proc.stdout}\nSTDERR:{proc.stderr[-3000:]}"
    return proc.stdout


def test_uncompressed_psum_matches_local():
    run_case("""
    ctx = TPContext(mesh=mesh, policy=NO_COMPRESSION)
    with set_mesh(mesh):
        y = jax.jit(lambda x, w: row_linear(ctx, x, w))(xs, w)
    assert rel(y, yl) < 1e-5, rel(y, yl)
    """)


def test_compressed_psum_error_within_fp4_bound():
    run_case("""
    ctx = TPContext(mesh=mesh, policy=PAPER_DEFAULT)
    with set_mesh(mesh):
        y = jax.jit(lambda x, w: row_linear(ctx, x, w))(xs, w)
    r = rel(y, yl)
    assert 0.0 < r < 0.2, r  # FP4 intrinsic error ~11% on gaussians
    """)


def test_two_phase_variant_close_to_gather():
    run_case("""
    two = dataclasses.replace(PAPER_DEFAULT, variant="two_phase")
    ctx = TPContext(mesh=mesh, policy=two)
    with set_mesh(mesh):
        y = jax.jit(lambda x, w: row_linear(ctx, x, w))(xs, w)
    r = rel(y, yl)
    assert 0.0 < r < 0.25, r  # ~sqrt(2) x gather error (double quantization)
    """)


def test_overlap_chunks_bit_identical_and_still_u8():
    """The chunked two-stage gather (policy.overlap_chunks > 1) reorders the
    quantize/transmit schedule, never the values: MX blocks are independent
    and chunk boundaries are block-aligned, so results are BIT-identical to
    the unchunked collective, the wire stays uint8, and a non-divisible
    request degrades to the largest feasible chunk count rather than
    changing semantics."""
    run_case("""
    from repro.core.collectives import _overlap_chunks
    ys = {}
    for n in (1, 2, 4):
        pol = dataclasses.replace(PAPER_DEFAULT, overlap_chunks=n)
        ctx = TPContext(mesh=mesh, policy=pol)
        with set_mesh(mesh):
            ys[n] = jax.jit(lambda x, w: row_linear(ctx, x, w))(xs, w)
    assert 0.0 < rel(ys[1], yl) < 0.2  # the codec really ran
    np.testing.assert_array_equal(np.asarray(ys[2]), np.asarray(ys[1]))
    np.testing.assert_array_equal(np.asarray(ys[4]), np.asarray(ys[1]))
    pol4 = dataclasses.replace(PAPER_DEFAULT, overlap_chunks=4)
    ctx4 = TPContext(mesh=mesh, policy=pol4)
    with set_mesh(mesh):
        txt = jax.jit(lambda x, w: row_linear(ctx4, x, w)).lower(xs, w).compile().as_text()
    gathers = re.findall(r'= (\\S+) all-gather\\(', txt)
    assert sum(g.startswith("u8[") for g in gathers) >= 4, gathers
    assert "all-reduce(" not in txt
    # chunk-count resolution: block-aligned divisor only, floor 1
    spec = PAPER_DEFAULT.spec  # block 32
    assert _overlap_chunks(256, spec, 4) == 4
    assert _overlap_chunks(256, spec, 3) == 2   # 3 !| 256 -> degrade
    assert _overlap_chunks(256, spec, 8) == 8   # 8*32 == 256 exactly
    assert _overlap_chunks(96, spec, 4) == 3    # 4 leaves 24 < block
    assert _overlap_chunks(32, spec, 4) == 1    # single block: unchunked
    """)


def test_hlo_uses_u8_allgather_not_allreduce():
    run_case("""
    ctx = TPContext(mesh=mesh, policy=PAPER_DEFAULT)
    with set_mesh(mesh):
        txt = jax.jit(lambda x, w: row_linear(ctx, x, w)).lower(xs, w).compile().as_text()
    gathers = re.findall(r'= (\\S+) all-gather\\(', txt)
    assert any(g.startswith("u8[") for g in gathers), gathers
    assert "all-reduce(" not in txt
    """)


def test_decode_gate_falls_back_to_psum():
    run_case("""
    ctx = TPContext(mesh=mesh, policy=PAPER_DEFAULT)  # min_tokens=8
    xd = xs[:, :1, :][:1]  # 1 token
    with set_mesh(mesh):
        txt = jax.jit(lambda x, w: row_linear(ctx, x, w)).lower(xd, w).compile().as_text()
    assert "all-reduce(" in txt
    """)


def test_batch_stays_sharded_inside_island():
    """The gathered compressed payload must be batch-LOCAL (8/2=4), not
    global — regression test for the partial-manual replication bug."""
    run_case("""
    ctx = TPContext(mesh=mesh, policy=PAPER_DEFAULT)
    with set_mesh(mesh):
        txt = jax.jit(lambda x, w: row_linear(ctx, x, w)).lower(xs, w).compile().as_text()
    payload = [g for g in re.findall(r'= u8\\[([\\d,]+)\\][^ ]* all-gather', txt)]
    assert payload, "no u8 gathers found"
    for dims in payload:
        b = int(dims.split(",")[1])
        assert b == 4, f"batch replicated inside island: {dims}"
    """)


def test_fused_mlp_island_parity():
    run_case("""
    wg = jnp.asarray(rng.normal(size=(256, 512)) / 16, jnp.float32)
    wu = jnp.asarray(rng.normal(size=(256, 512)) / 16, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(512, 256)) / 22, jnp.float32)
    ctx = TPContext(mesh=mesh, policy=NO_COMPRESSION)
    with set_mesh(mesh):
        ym = jax.jit(lambda x: fused_mlp(ctx, x, wg, wu, wd))(xs)
    yl2 = fused_mlp(ctx_l, x, wg, wu, wd)
    assert rel(ym, yl2) < 1e-4, rel(ym, yl2)
    """)


def test_moe_island_parity():
    run_case("""
    from repro.models.moe import moe, init_moe
    from repro.models.common import Initializer
    from repro.configs import get_config, reduced_config
    cfg = reduced_config(get_config("jamba-v0.1-52b"))
    cfg = dataclasses.replace(cfg, n_experts=4, top_k=2, capacity_factor=2.0,
                              dtype="float32")
    init = Initializer(jax.random.PRNGKey(0), jnp.float32)
    p = init_moe(init, "moe", cfg)
    xb = jnp.asarray(rng.normal(size=(8, 16, cfg.d_model)), jnp.float32)
    out_l, _ = moe(ctx_l, p, xb, cfg)
    ctx = TPContext(mesh=mesh, policy=NO_COMPRESSION)
    with set_mesh(mesh):
        xbs = jax.device_put(xb, NamedSharding(mesh, P("data", None, None)))
        out_m, _ = jax.jit(lambda x: moe(ctx, p, x, cfg))(xbs)
    assert rel(out_m, out_l) < 1e-4, rel(out_m, out_l)
    """)


def test_ste_gradient_flows_through_compressed_psum():
    run_case("""
    ctx = TPContext(mesh=mesh, policy=dataclasses.replace(PAPER_DEFAULT, min_tokens=1))
    def loss(w):
        return jnp.sum(row_linear(ctx, xs, w) ** 2)
    with set_mesh(mesh):
        g = jax.jit(jax.grad(loss))(w)
    gn = float(jnp.linalg.norm(g))
    assert np.isfinite(gn) and gn > 0, gn
    # STE: gradient points the same way as the uncompressed gradient
    # (FP4 noise passes through the quadratic loss, so compare direction)
    ctx0 = TPContext(mesh=mesh, policy=NO_COMPRESSION)
    def loss0(w):
        return jnp.sum(row_linear(ctx0, xs, w) ** 2)
    with set_mesh(mesh):
        g0 = jax.jit(jax.grad(loss0))(w)
    cos = float(jnp.sum(g * g0) / (jnp.linalg.norm(g) * jnp.linalg.norm(g0)))
    assert cos > 0.7, cos
    """)


# -------------------------------------------------- single-process checks
# (a 1-device mesh gives real axis semantics without the subprocess cost)


def _one_device_island(fn, out_extra_dim=False):
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("model",))
    out_specs = P(*((None,) * (3 + int(out_extra_dim))))
    return jax.shard_map(fn, mesh=mesh, in_specs=P(None, None, None),
                         out_specs=out_specs, axis_names={"model"},
                         check_vma=False)


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("dtype", ["bfloat16", "float16"])
def test_compressed_all_gather_preserves_dtype(use_pallas, dtype):
    """Regression: compressed_all_gather leaked the dequantizer's fp32
    instead of casting back to x.dtype (unlike compressed_psum /
    compressed_all_to_all) — over both the jnp and Pallas codecs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.collectives import compressed_all_gather
    from repro.core.formats import MXSpec

    spec = MXSpec.make("fp4_e2m1", 32, "e8m0")
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 64)),
                    jnp.dtype(dtype))
    f = _one_device_island(
        lambda xl: compressed_all_gather(xl, "model", spec,
                                         use_pallas=use_pallas),
        out_extra_dim=True)
    y = jax.jit(f)(x)
    assert y.dtype == x.dtype, (y.dtype, x.dtype)
    assert y.shape == (1, *x.shape)


def test_two_phase_downgrade_warns_once_and_strict_raises():
    """variant='two_phase' with axis_size unplumbed (or a non-dividing
    feature dim) must not silently run the gather variant: warn once, or
    raise when strict."""
    import warnings

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import collectives
    from repro.core.collectives import compressed_psum
    from repro.core.formats import MXSpec

    spec = MXSpec.make("fp4_e2m1", 32, "e8m0")
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 64)),
                    jnp.float32)

    # strict: raises at trace time, before any collective is issued
    with pytest.raises(ValueError, match="two_phase"):
        compressed_psum(x, "model", spec, variant="two_phase", axis_size=0,
                        strict=True)

    collectives._DOWNGRADE_WARNED.clear()
    f = _one_device_island(
        lambda xl: compressed_psum(xl, "model", spec, variant="two_phase",
                                   axis_size=0))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        jax.jit(f)(x).block_until_ready()
    assert any("two_phase" in str(w.message) for w in caught), caught
    # warned once per distinct reason: a second trace stays quiet
    with warnings.catch_warnings(record=True) as caught2:
        warnings.simplefilter("always")
        g = _one_device_island(
            lambda xl: compressed_psum(xl * 2, "model", spec,
                                       variant="two_phase", axis_size=0))
        jax.jit(g)(x).block_until_ready()
    assert not any("two_phase" in str(w.message) for w in caught2), caught2


def test_two_phase_downgrade_warns_per_site_not_per_process():
    """Regression: the downgrade warning used to dedup on the reason string
    alone, so ONE engine's fallback silenced every later engine's — a second
    policy/shape hitting the same downgrade reason must warn again, while the
    exact same site stays deduped."""
    import warnings

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import collectives
    from repro.core.collectives import compressed_psum, reset_downgrade_warnings
    from repro.core.formats import MXSpec

    rng = np.random.default_rng(1)
    x64 = jnp.asarray(rng.normal(size=(2, 8, 64)), jnp.float32)
    x128 = jnp.asarray(rng.normal(size=(2, 8, 128)), jnp.float32)
    spec_a = MXSpec.make("fp4_e2m1", 32, "e8m0")
    spec_b = MXSpec.make("fp5_e2m2", 16, "e8m0")

    def trace(x, spec):
        f = _one_device_island(
            lambda xl: compressed_psum(xl, "model", spec, variant="two_phase",
                                       axis_size=0))
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            jax.make_jaxpr(f)(x)
        return [w for w in caught if "two_phase" in str(w.message)]

    reset_downgrade_warnings()
    assert len(trace(x64, spec_a)) == 1          # first site warns
    assert len(trace(x64, spec_a)) == 0          # same site: deduped
    assert len(trace(x64, spec_b)) == 1          # same reason, other policy
    assert len(trace(x128, spec_a)) == 1         # same reason, other shape
    reset_downgrade_warnings()
    assert len(trace(x64, spec_a)) == 1          # reset forgets the history
    assert collectives._DOWNGRADE_WARNED          # and repopulates
    reset_downgrade_warnings()


def _element_format_names():
    from repro.core.formats import ELEMENT_FORMATS  # jax-free module

    return sorted(ELEMENT_FORMATS)


@pytest.mark.parametrize("fmt", _element_format_names())
def test_wire_payload_matches_wire_arrays_shape(fmt):
    """Satellite contract test: for EVERY registered MX element format, what
    compressed_all_gather / compressed_psum actually put on the wire (the
    uint8 all_gather operands in the traced island) is byte-for-byte the
    ``wire_arrays_shape`` prediction — payload lastdim n*bits/8, one scale
    byte per block."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.collectives import compressed_all_gather, compressed_psum
    from repro.core.formats import MXSpec
    from repro.core.mx import wire_arrays_shape
    from repro.staticcheck import collect_collectives

    block = 8
    n = 64  # divisible by 8 blocks and by 8/bits packing for every format
    spec = MXSpec.make(fmt, block, "e8m0")
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 3, n)),
                    jnp.float32)
    payload_shape, scales_shape = wire_arrays_shape(x.shape, spec)

    for name, fn in [
        ("all_gather", lambda xl: compressed_all_gather(xl, "model", spec)),
        ("psum", lambda xl: compressed_psum(xl, "model", spec)),
    ]:
        island = _one_device_island(fn, out_extra_dim=(name == "all_gather"))
        jaxpr = jax.make_jaxpr(island)(x)
        u8 = [r for r in collect_collectives(jaxpr.jaxpr)
              if r.dtype == "uint8"]
        assert len(u8) == 2, (fmt, name, u8)
        payload, scales = u8
        assert payload.shape == payload_shape, (fmt, name, payload)
        assert scales.shape == scales_shape, (fmt, name, scales)
        assert payload.bytes_per_device == np.prod(payload_shape)
        assert scales.bytes_per_device == np.prod(scales_shape)
        # no dense float of x's wire size leaks alongside the compressed pair
        assert not any(r.dtype == "float32" and r.shape[-1] == n
                       for r in collect_collectives(jaxpr.jaxpr)), (fmt, name)


def test_compressed_all_gather_roundtrip():
    run_case("""
    from repro.core.collectives import compressed_all_gather
    spec = MXSpec.make("fp5_e2m2", 16, "e8m0")
    def f(x):
        def island(xl):
            return compressed_all_gather(xl, "model", spec)
        return jax.shard_map(island, mesh=mesh, in_specs=P(None, None, "model"),
                             out_specs=P(None, None, None, "model"),
                             axis_names={"model"}, check_vma=False)(x)
    with set_mesh(mesh):
        g = jax.jit(f)(x)
    # device j's slice of gathered shard i holds shard i's features
    for i in range(4):
        got = g[i][..., i * 64 : (i + 1) * 64]
        want = x[..., i * 64 : (i + 1) * 64]
        assert rel(got, want) < 0.1, (i, rel(got, want))
    """)
