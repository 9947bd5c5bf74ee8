import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first lines: jax locks device count on first init. 512
# placeholder host devices back the production meshes; nothing is allocated
# (lower/compile on ShapeDtypeStructs only).

"""Multi-pod dry-run: prove every (architecture x input shape x mesh)
combination lowers AND compiles under the production sharding config, and
extract the roofline inputs (FLOPs / bytes / collective bytes / memory).

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--compressed]

Results land in experiments/dryrun/*.json (read by EXPERIMENTS.md tooling
and benchmarks/roofline.py).
"""
import argparse
import json
import pathlib
import time
import traceback
from typing import Optional

import jax

from repro.analysis.roofline import analyze_compiled
from repro.configs import ASSIGNED, INPUT_SHAPES, get_config
from repro.core.policy import CompressionPolicy, NO_COMPRESSION, PAPER_DEFAULT
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import input_shardings, make_context
from repro.models.model import Model
from repro.serving.kv_cache import cache_specs
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import make_train_step, train_state_specs

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# long_500k applicability (DESIGN.md §Arch-applicability)
LONG_OK = {"jamba-v0.1-52b", "xlstm-125m", "gemma3-4b", "mixtral-8x22b"}


def skip_reason(arch: str, shape_name: str) -> Optional[str]:
    if shape_name == "long_500k" and arch not in LONG_OK:
        return "pure full-attention arch: long_500k requires sub-quadratic attention"
    return None


def _sharded_sds(tree_shapes, tree_specs, mesh):
    from jax.sharding import NamedSharding

    from repro.launch.sharding import resolve_specs

    tree_specs = resolve_specs(tree_shapes, tree_specs, mesh)

    def one(sds, spec):
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype,
                                    sharding=NamedSharding(mesh, spec))

    return jax.tree.map(one, tree_shapes, tree_specs,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool = False,
                policy: CompressionPolicy = PAPER_DEFAULT,
                scan_layers: bool = True, fuse_mlp: bool = False,
                ring_cache: bool = False, verbose: bool = True):
    """Lower + compile one combination; returns the roofline record."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 1
    for s in mesh.shape.values():
        n_chips *= s
    # scan-over-layers only for training: the serve paths' per-layer caches
    # as scan xs trip an XLA-CPU SPMD crash (AllReducePromotion on resharded
    # stacked caches); unrolled serve graphs compile fine and faster anyway
    scan_layers = scan_layers and shape.kind == "train"
    ctx = make_context(mesh, shape, policy=policy, scan_layers=scan_layers,
                       remat=(shape.kind == "train"), fuse_mlp_island=fuse_mlp)
    model = Model(cfg)

    params_shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params_sds = _sharded_sds(params_shapes, model.param_specs(ctx), mesh)
    batch_sds = input_shardings(ctx, model.input_specs(shape))

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            from repro.training.optimizer import OptState, init_opt_state

            step_fn = make_train_step(model, ctx, AdamWConfig())
            state_shapes = {
                "params": params_shapes,
                "opt": jax.eval_shape(init_opt_state, params_shapes),
            }
            state_sds = _sharded_sds(state_shapes, train_state_specs(model, ctx), mesh)
            lowered = jax.jit(step_fn, donate_argnums=(0,)).lower(state_sds, batch_sds)
            tokens = shape.global_batch * shape.seq_len
            train = True
        else:
            cache_shapes = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len)
            )
            cache_sds = _sharded_sds(cache_shapes, cache_specs(ctx, cache_shapes), mesh)
            if shape.kind == "prefill":
                fn = lambda p, b, c: model.prefill(ctx, p, b, c)
                tokens = shape.global_batch * shape.seq_len
            else:
                fn = lambda p, b, c: model.decode_step(ctx, p, b["tokens"], c)
                tokens = shape.global_batch
            # donate the cache: in-place update, as the serving engine does
            lowered = jax.jit(fn, donate_argnums=(2,)).lower(
                params_sds, batch_sds, cache_sds)
            train = False
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    record = analyze_compiled(compiled, n_chips=n_chips, cfg=cfg, tokens=tokens,
                              train=train)
    record.update({
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "policy": policy.describe(),
        "compressed": policy.enabled,
        "scan_layers": scan_layers,
        "fuse_mlp": fuse_mlp,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    })
    if verbose:
        mem_gb = record["memory"]["peak_est_bytes"] / 2**30
        print(
            f"OK {arch:26s} {shape_name:12s} {record['mesh']:8s} "
            f"{'MX' if policy.enabled else 'bf16':4s} "
            f"flops/chip={record['hlo_flops_per_chip']:.3e} "
            f"coll={record['collective_bytes_per_chip']:.3e}B "
            f"mem~{mem_gb:.2f}GiB dom={record['dominant']} "
            f"compile={t_compile:.1f}s"
        )
    return record


def save_record(record: dict, suffix: str = "") -> pathlib.Path:
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    tag = "mx" if record["compressed"] else "bf16"
    name = f"{record['arch']}__{record['shape']}__{record['mesh']}__{tag}{suffix}.json"
    path = OUT_DIR / name.replace("/", "_")
    path.write_text(json.dumps(record, indent=1))
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--compressed", action="store_true", default=True)
    ap.add_argument("--uncompressed", dest="compressed", action="store_false")
    ap.add_argument("--both-policies", action="store_true")
    ap.add_argument("--no-scan", dest="scan", action="store_false", default=True)
    ap.add_argument("--fuse-mlp", action="store_true")
    args = ap.parse_args()

    archs = ASSIGNED if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    policies = ([NO_COMPRESSION, PAPER_DEFAULT] if args.both_policies
                else [PAPER_DEFAULT if args.compressed else NO_COMPRESSION])

    failures = []
    for arch in archs:
        for shape in shapes:
            reason = skip_reason(arch, shape)
            if reason:
                print(f"SKIP {arch:26s} {shape:12s} — {reason}")
                continue
            for mp in meshes:
                for pol in policies:
                    try:
                        rec = lower_combo(arch, shape, multi_pod=mp, policy=pol,
                                          scan_layers=args.scan,
                                          fuse_mlp=args.fuse_mlp)
                        save_record(rec)
                    except Exception as e:  # a failure here is a sharding bug
                        traceback.print_exc()
                        failures.append((arch, shape, mp, str(e)))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nAll dry-run combinations lowered + compiled.")


if __name__ == "__main__":
    main()
