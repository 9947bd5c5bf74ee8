"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

The TPU compiler is installed alongside JAX, so the main-path kernels are
compiled here for a ``v5e:2x2`` topology that is described, not attached, at
the widths the engine serves: the paged-attention kernel at internlm2-1.8b
widths (16 query / 8 KV heads of 128, 16-token blocks) in the decode and
mixed geometries, dense and FP4 pools; the MX codec at qwen2-7b's
row-parallel width (3584 features). Mosaic refuses what interpret mode
accepts (block shapes off the (8, 128) tiling, casts the chip lacks), so
each compile asserts the kernel really lowered (``tpu_custom_call``).

The topology is described only inside the module fixture: describing it
loads the TPU library, which one process at a time may hold.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, SingleDeviceSharding

from repro.core.formats import MXSpec
from repro.core.mx import MXCompressed
from repro.kernels.mx_dequant import dequant_reduce, mx_dequantize_2d
from repro.kernels.mx_quant import mx_quantize_2d
from repro.kernels.paged_attention import paged_attention
from repro.launch.mesh import make_host_mesh, make_kv_mesh, make_mesh

FP4 = MXSpec.make("fp4_e2m1", 32, "e8m0")
# internlm2-1.8b paged reads: 4 slots of 544 positions in 16-token blocks,
# a 36-token mixed budget (32-token chunk + one decode token per slot)
KV, H, HD, BS, SLOTS, T = 8, 16, 128, 16, 4, 36
NB = 544 // BS
POOL_BLOCKS = SLOTS * NB + 1
# qwen2-7b row-parallel collective payload: the 36-token budget x d_model
TOKENS, FEATURES, TP = 36, 3584, 4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("pool", ["dense", "fp4"])
@pytest.mark.parametrize("geometry", ["decode", "mixed"])
def test_paged_attention_compiles_for_v5e(one_chip, pool, geometry):
    s = lambda shape, dtype: _sds(one_chip, shape, dtype)
    kv_dim = KV * HD
    if pool == "dense":
        spec = None
        pk = pv = s((POOL_BLOCKS, BS, kv_dim), jnp.bfloat16)
    else:
        spec = FP4
        pk = pv = MXCompressed(s((POOL_BLOCKS, BS, kv_dim // 2), jnp.uint8),
                               s((POOL_BLOCKS, BS, kv_dim // 32), jnp.uint8))
    rows = SLOTS if geometry == "decode" else T
    args = [s((rows, 1, H * HD), jnp.bfloat16), pk, pv,
            s((rows, NB), jnp.int32), s((rows,), jnp.int32),
            s((rows, 1), jnp.int32)]
    if geometry == "mixed":  # the step's own K/V ride along as extras
        args += [s((T, kv_dim), jnp.bfloat16), s((T, kv_dim), jnp.bfloat16),
                 s((T, T), jnp.int32)]

    def read(q, pk, pv, tables, hist, q_pos, *extras):
        return paged_attention(q, pk, pv, tables, hist, q_pos, *extras,
                               spec=spec, kv_heads=KV, scale=HD**-0.5,
                               out_dtype=jnp.bfloat16)

    assert "tpu_custom_call" in _compiled_text(read, *args)


@pytest.mark.parametrize("kernel", ["quantize", "dequantize", "dequant_reduce"])
def test_mx_codec_compiles_for_v5e(one_chip, kernel):
    s = lambda shape, dtype: _sds(one_chip, shape, dtype)
    payload, scales = (TOKENS, FEATURES // 2), (TOKENS, FEATURES // 32)
    if kernel == "quantize":
        text = _compiled_text(lambda x: mx_quantize_2d(x, FP4),
                              s((TOKENS, FEATURES), jnp.bfloat16))
    elif kernel == "dequantize":
        text = _compiled_text(lambda p, c: mx_dequantize_2d(p, c, FP4),
                              s(payload, jnp.uint8), s(scales, jnp.uint8))
    else:  # the gathered TP shards, fused decompress + sum
        text = _compiled_text(lambda p, c: dequant_reduce(p, c, FP4),
                              s((TP,) + payload, jnp.uint8),
                              s((TP,) + scales, jnp.uint8))
    assert "tpu_custom_call" in text


def test_mesh_helpers_build_auto_axes():
    """Every mesh the repo builds is Auto-typed: GSPMD propagates the
    NamedSharding placements, and indexing a sharded array stays legal."""
    meshes = [make_mesh((1,), ("model",)), make_host_mesh(),
              make_kv_mesh(kv=1)]
    for mesh in meshes:
        assert all(t == AxisType.Auto for t in mesh.axis_types), mesh
    x = jax.device_put(jnp.arange(8.0), jax.sharding.NamedSharding(
        meshes[0], jax.sharding.PartitionSpec("model")))
    assert float(x[3]) == 3.0
