"""The mixed step's jnp history read, indexed by query tile
(``models/attention.py:_read_tiles`` / ``_attend_tiled``).

Pins: equality with the per-token formulation (kept here only as the
oracle: every flattened row gathers its slot's full history and attends it
concatenated with the step's K/V) across packings and pools, replicated and
kv-sharded; the static tile count bounds every packing the engine can build,
and the host's count of used tiles equals the program's; and no
intermediate of the jnp read reaches ``token_budget x capacity x kv_dim``
elements, which the per-token read did.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mx
from repro.core.formats import KVCacheSpec, MXSpec
from repro.core.mx import MXCompressed
from repro.core.tp import TPContext
from repro.models.attention import (
    NEG_INF, READ_TILE, _mixed_read_attend_append, _read_tiles,
    mixed_read_tiles, read_tiles_held, read_tiles_used,
)
from repro.serving import build_mixed_batch
from repro.staticcheck.jaxpr_audit import iter_eqns
from tests.conftest import fp32_reduced

CTX = TPContext(mesh=None)
L = READ_TILE
NS, NB, BS = 4, 4, 16                  # slots, table columns, block size
CAP = NB * BS
T = 32                                 # token budget: 7 tiles for 4 slots

# each packing: prefill segments (slot, length) then decode slots
PACKINGS = {
    "all-decode": ([], [0, 1, 2, 3]),
    "chunk+decodes": ([(2, 16)], [0, 1, 3]),
    "short-segments": ([(0, L - 1), (1, L), (2, L + 1), (3, 1)], []),
    "all-N-tiles": ([(1, L + 1), (3, L + 1), (0, L + 1)], [2]),
    "pads-only": ([], []),
}
POOLS = ["f32", "bf16", "fp4_e2m1", "f32-window"]


def _cfg():
    # GQA with 4 query heads per KV head, so head grouping is exercised
    return dataclasses.replace(fp32_reduced("internlm2-1.8b"), n_heads=8,
                               n_kv_heads=2)


def _plan(name, seed):
    """A packing through ``build_mixed_batch``, with histories drawn so
    every segment fits its slot's capacity."""
    rng = np.random.default_rng(seed)
    segs, dec = PACKINGS[name]
    starts = rng.integers(1, CAP - 2 * L, size=NS).astype(np.int32)
    batch = build_mixed_batch(
        [(s, np.zeros(n, np.int32), int(starts[s])) for s, n in segs],
        [(s, 0, int(starts[s])) for s in dec], T, NS)
    return batch, starts


def _inputs(cfg, pool, seed=0):
    rng = np.random.default_rng(seed)
    n_blocks = NS * NB + 2             # divides over two kv shards
    tables = jnp.asarray(
        1 + rng.permutation(NS * NB).reshape(NS, NB), jnp.int32)
    kv = [rng.normal(size=(n_blocks, BS, cfg.kv_dim)) for _ in range(2)]
    q = jnp.asarray(rng.normal(size=(1, T, cfg.n_heads, cfg.head_dim)),
                    jnp.float32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(1, T, cfg.kv_dim)),
                                jnp.float32) for _ in range(2))
    if pool == "fp4_e2m1":
        spec = KVCacheSpec(mx=MXSpec.make("fp4_e2m1", 32, "e8m0"))
        pools = [mx.quantize(jnp.asarray(a, jnp.float32), spec.mx)
                 for a in kv]
    else:
        spec = KVCacheSpec()
        dt = jnp.bfloat16 if pool == "bf16" else jnp.float32
        pools = [jnp.asarray(a, dt) for a in kv]
    window = 24 if pool == "f32-window" else None
    return q, k_new, v_new, tables, pools, spec, window


def per_token_read(cfg, q, k_new, v_new, *, positions, slot_ids,
                   slot_starts, valid, is_decode, tables, pool_k, pool_v,
                   window, cache_spec):
    """The per-token formulation: row t gathers ``tables[slot_ids[t]]``'s
    full history and attends it concatenated with all T step keys."""
    Tq = q.shape[1]
    H, hd, KV = cfg.n_heads, cfg.head_dim, cfg.n_kv_heads
    quantized = cache_spec.quantized
    my = tables[slot_ids]
    bs = (pool_k.payload if quantized else pool_k).shape[1]
    cap = my.shape[1] * bs

    def hist(pool):
        if quantized:
            wire = MXCompressed(pool.payload[my].reshape(Tq, cap, -1),
                                pool.scales[my].reshape(Tq, cap, -1))
            return mx.dequantize(wire, cache_spec.mx, out_dtype=q.dtype)
        return pool[my].reshape(Tq, cap, -1).astype(q.dtype)

    def read_back(new, pool):
        if quantized:
            return mx.dequantize(mx.quantize(new, cache_spec.mx),
                                 cache_spec.mx, out_dtype=q.dtype)
        return new.astype(pool.dtype).astype(q.dtype)

    k_step = jnp.where(is_decode[:, None], read_back(k_new[0], pool_k),
                       k_new[0])
    v_step = jnp.where(is_decode[:, None], read_back(v_new[0], pool_v),
                       v_new[0])
    big = jnp.int32(2**30)
    start = slot_starts[slot_ids]
    t_hist = jnp.arange(cap, dtype=jnp.int32)[None, :]
    t_hist = jnp.where(t_hist < start[:, None], t_hist, big)
    same = (slot_ids[None, :] == slot_ids[:, None]) & valid[None, :]
    t_pos = jnp.concatenate(
        [t_hist, jnp.where(same, positions[None, :], big)], axis=1)
    k_all = jnp.concatenate(
        [hist(pool_k), jnp.broadcast_to(k_step, (Tq,) + k_step.shape)], 1)
    v_all = jnp.concatenate(
        [hist(pool_v), jnp.broadcast_to(v_step, (Tq,) + v_step.shape)], 1)
    qg = q[0].reshape(Tq, KV, H // KV, hd)
    s = jnp.einsum("tkgd,tckd->tkgc", qg, k_all.reshape(Tq, -1, KV, hd),
                   preferred_element_type=jnp.float32) * hd**-0.5
    ok = t_pos <= positions[:, None]
    if window is not None:
        ok = ok & (t_pos > positions[:, None] - window)
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_all.dtype)
    out = jnp.einsum("tkgc,tckd->tkgd", p, v_all.reshape(Tq, -1, KV, hd))
    return out.reshape(Tq, H * hd)


def _batch_args(batch, starts, tables, pools, spec, window):
    return dict(positions=jnp.asarray(batch.positions),
                slot_ids=jnp.asarray(batch.slot_ids),
                slot_starts=jnp.asarray(starts),
                valid=jnp.asarray(batch.valid),
                is_decode=jnp.asarray(batch.is_decode), tables=tables,
                pool_k=pools[0], pool_v=pools[1], window=window,
                cache_spec=spec)


@pytest.mark.parametrize("pool", POOLS)
@pytest.mark.parametrize("packing", sorted(PACKINGS))
def test_tiled_read_matches_per_token_read(packing, pool):
    """Every real row's attention output equals the per-token formulation's
    within f32 tolerance; pad rows read zero."""
    cfg = _cfg()
    q, k_new, v_new, tables, pools, spec, window = _inputs(cfg, pool)
    batch, starts = _plan(packing, seed=1)
    args = _batch_args(batch, starts, tables, pools, spec, window)
    out, _, _ = _mixed_read_attend_append(
        CTX, cfg, q, k_new, v_new, **args)
    ref = per_token_read(cfg, q, k_new, v_new, **args)
    out = np.asarray(out[0])
    real = batch.valid
    assert (out[~real] == 0).all()
    np.testing.assert_allclose(out[real], np.asarray(ref)[real],
                               rtol=1e-5, atol=1e-5)
    if packing == "all-N-tiles":
        tile, _, _ = _read_tiles(args["slot_ids"], args["valid"], NS)
        assert int(jnp.max(jnp.where(args["valid"], tile, -1))) + 1 \
            == mixed_read_tiles(T, NS)


def test_tiled_read_matches_per_token_read_kv_sharded():
    """The kv-sharded branch indexes its per-slot virtual regions by
    ``tile_slot``: on a kv=2 mesh of host devices, dense and fp4 wire
    pools, every packing equals the per-token formulation, pads zero."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.policy import NO_COMPRESSION
        from repro.launch.mesh import make_kv_mesh
        from repro.launch.sharding import make_context
        from repro.models.attention import _mixed_read_attend_append
        from tests import test_tiled_read as t

        ctx = make_context(make_kv_mesh(kv=2, data=1, model=1), None,
                           policy=NO_COMPRESSION, kv_axis="kv")
        assert ctx.kv_shards == 2
        cfg = t._cfg()
        for pool in ["f32", "fp4_e2m1"]:
            q, k_new, v_new, tables, pools, spec, window = t._inputs(cfg, pool)
            for packing in sorted(t.PACKINGS):
                batch, starts = t._plan(packing, seed=2)
                args = t._batch_args(batch, starts, tables, pools, spec,
                                     window)
                fn = jax.jit(lambda q, k, v, a: _mixed_read_attend_append(
                    ctx, cfg, q, k, v, window=window, cache_spec=spec, **a))
                arr = {k: v for k, v in args.items()
                       if k not in ("window", "cache_spec")}
                out, _, _ = fn(q, k_new, v_new, arr)
                ref = t.per_token_read(cfg, q, k_new, v_new, **args)
                real = batch.valid
                np.testing.assert_allclose(
                    np.asarray(out[0])[real], np.asarray(ref)[real],
                    rtol=1e-5, atol=1e-5, err_msg=f"{pool} {packing}")
                assert (np.asarray(out[0])[~real] == 0).all(), packing
        print("ok")
    """)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(["src", "."]),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, env=env, cwd=root, timeout=600)
    assert proc.returncode == 0, (
        f"STDOUT:{proc.stdout}\nSTDERR:{proc.stderr[-4000:]}")


# -------------------------------------------------------------- tile bound


@pytest.mark.parametrize("n_slots,chunk", [(4, 16), (8, 8), (5, 24), (64, 128)])
def test_static_tile_count_bounds_every_engine_packing(n_slots, chunk):
    """Random plans under the engine's invariants (``token_budget >=
    n_slots + prefill_chunk``, each slot once, prefill segments of at most
    a chunk, decode segments of one) never fill more than the static tile
    count, and the host's ``read_tiles_used`` equals the tiles the program
    maps rows onto."""
    rng = np.random.default_rng(n_slots * 1000 + chunk)
    map_tiles = jax.jit(_read_tiles, static_argnums=2)
    for budget in (n_slots + chunk, n_slots + chunk + 3, 2 * (n_slots + chunk)):
        n_tiles = mixed_read_tiles(budget, n_slots)
        for _ in range(40):
            slots = rng.permutation(n_slots)[:rng.integers(0, n_slots + 1)]
            decode = [int(s) for s in slots if rng.random() < 0.5]
            left = budget - len(decode)
            segs = []
            for s in slots:
                if s in decode:
                    continue
                n = int(rng.integers(1, chunk + 1))
                if rng.random() < 0.5:
                    n = min(n, left)   # fill the budget to its end
                if 0 < n <= left:
                    segs.append((int(s), np.zeros(n, np.int32), 0))
                    left -= n
            batch = build_mixed_batch(segs, [(s, 0, 0) for s in decode],
                                      budget, n_slots)
            used = read_tiles_used([len(t) for _, t, _ in segs]
                                   + [1] * len(decode))
            assert used <= n_tiles
            tile, lane, tile_slot = map_tiles(
                jnp.asarray(batch.slot_ids), jnp.asarray(batch.valid),
                n_slots)
            tile, lane = np.asarray(tile), np.asarray(lane)
            real = batch.valid
            assert tile_slot.shape == (n_tiles,)
            assert (tile[~real] == n_tiles).all()
            assert len({(a, b) for a, b in zip(tile[real], lane[real])}) \
                == real.sum()                       # one row per lane
            assert len(set(tile[real].tolist())) == used
            assert (np.asarray(tile_slot)[tile[real]]
                    == batch.slot_ids[real]).all()


# ------------------------------------------------------------ static shape


def test_jnp_read_holds_no_budget_by_capacity_intermediate():
    """No intermediate of the jnp mixed read has token_budget x capacity x
    kv_dim elements or more; the per-token formulation has one."""
    cfg = _cfg()
    budget, n_slots, nb = 48, 8, 16
    limit = budget * nb * BS * cfg.kv_dim
    f32, i32 = jnp.float32, jnp.int32
    sd = jax.ShapeDtypeStruct
    pool = sd((n_slots * nb + 1, BS, cfg.kv_dim), jnp.bfloat16)
    shapes = dict(positions=sd((budget,), i32), slot_ids=sd((budget,), i32),
                  slot_starts=sd((n_slots,), i32),
                  valid=sd((budget,), jnp.bool_),
                  is_decode=sd((budget,), jnp.bool_),
                  tables=sd((n_slots, nb), i32), pool_k=pool, pool_v=pool)
    q = sd((1, budget, cfg.n_heads, cfg.head_dim), f32)
    kv = sd((1, budget, cfg.kv_dim), f32)

    def largest(fn):
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, a: fn(cfg, q, k, v, window=None,
                                  cache_spec=KVCacheSpec(), **a))(
            q, kv, kv, shapes)
        return max(int(np.prod(o.aval.shape)) for e in iter_eqns(jaxpr)
                   for o in e.outvars if hasattr(o.aval, "shape"))

    tiled = largest(lambda cfg, *a, **k: _mixed_read_attend_append(
        CTX, cfg, *a, **k))
    assert tiled < limit, (tiled, limit)
    assert largest(per_token_read) >= limit


# ----------------------------------------------------------------- counter


@pytest.mark.parametrize("spec,held", [
    (None, 7), ("bf16", 7), ("fp4_e2m1", 7), ("fp4_e2m1+pallas", 0)])
def test_read_tiles_held_follows_the_read_path(spec, held):
    """The jnp read holds ``mixed_read_tiles`` tiles a step; the Pallas
    kernel, which walks each row's table, holds none."""
    cache_spec = None if spec is None else KVCacheSpec.parse(spec)
    assert read_tiles_held(T, NS, cache_spec) == held
    assert mixed_read_tiles(T, NS) == 7


def test_read_tile_share_counts_the_mixed_jnp_read():
    """``ServeStats`` counts the tiles that hold a real row against the
    tiles the fixed-shape read holds, on mixed jnp engines only."""
    from repro.models.model import Model
    from repro.serving import Engine, Request

    cfg = fp32_reduced("internlm2-1.8b")
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    def run(token_budget):
        eng = Engine(model, params, CTX, max_slots=2, max_len=64,
                     block_size=16, cache_dtype=jnp.float32,
                     prefill_chunk=16, token_budget=token_budget)
        eng.run([Request(prompt=np.arange(5 + 13 * i, dtype=np.int32),
                         max_new_tokens=4) for i in range(3)])
        return eng.stats

    st = run(18)
    assert st.read_tiles_held == st.n_steps * mixed_read_tiles(18, 2)
    assert st.n_steps <= st.read_tiles < st.read_tiles_held
    assert st.summary()["read_tile_share"] == st.read_tiles / st.read_tiles_held
    assert run(0).summary()["read_tile_share"] == 0.0     # split steps
