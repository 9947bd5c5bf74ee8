#!/usr/bin/env python
"""Static program audit — the CI gate over the compression contract.

Runs ``repro.staticcheck`` end to end (DESIGN.md §Static analysis):

1. **Jaxpr audit** over the dense+fp4 × split+mixed engine matrix on a
   1-device TP mesh (real ``"model"`` axis semantics in-process — the
   collectives are present in the jaxpr without a multi-device runtime),
   printing the per-program collective/bytes table and failing on any rule
   hit (dense collective in a compressed program, wire-shape mismatch,
   boundary dtype drift, host transfer in a step program, nondeterministic
   retrace).
2. With ``--tp-mesh``: the same audit re-run in a subprocess with 8 forced
   host devices on the production-shaped ``data×model`` mesh, where the TP
   axis size is > 1 and gathered byte counts are real — and AGAIN on a
   ``kv×data×model`` mesh with sequence-sharded pools (``kv_shards=2``),
   where every matrix row additionally arms the ``pool-reshard`` rule (no
   step program may rebuild a replicated full-capacity pool).
3. **AST lint** (rules SC001–SC006) over ``src/repro`` + ``scripts``.
4. **jit static-arg audit** over ``src/repro`` (rule SC004 via the shared
   resolver — every ``static_argnames`` signature derived statically).

Exit status: 0 when every pass is green, 1 otherwise.

  PYTHONPATH=src python scripts/static_audit.py            # audits + lint
  PYTHONPATH=src python scripts/static_audit.py --tp-mesh  # + subprocess TP
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))


ENGINE_MATRIX = [
    # (label, cache_spec, token_budget) — dense+fp4 × split+mixed, plus the
    # gather-free Pallas read path (+pallas): the audit recurses into the
    # pallas_call kernel jaxpr and additionally enforces the pool-gather rule
    ("dense-mixed", None, None),
    ("dense-split", None, 0),
    ("fp4-mixed", "fp4_e2m1", None),
    ("fp4-split", "fp4_e2m1", 0),
    ("dense-mixed-pallas", "bf16+pallas", None),
    ("fp4-mixed-pallas", "fp4_e2m1+pallas", None),
]


def audit_matrix(arch: str, mesh, ctx, *, stream=sys.stdout) -> bool:
    """Audit every engine config of the matrix under ``ctx``; print tables."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import Model
    from repro.serving import Engine
    from repro.staticcheck import audit_engine

    cfg = _reduced_cfg(arch)
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ok = True
    with jax.set_mesh(mesh):
        for label, cache_spec, token_budget in ENGINE_MATRIX:
            kw = {} if token_budget is None else {"token_budget": token_budget}
            eng = Engine(model, params, ctx, max_slots=2, max_len=64,
                         cache_dtype=jnp.float32, cache_spec=cache_spec,
                         prefill_chunk=8, **kw)
            report = audit_engine(eng, label=f"{arch} {label}", prompt_len=16)
            print(report.format_table(), file=stream)
            print(file=stream)
            ok &= report.ok
    return ok


def _reduced_cfg(arch: str):
    from repro.configs import get_config, reduced_config

    return dataclasses.replace(reduced_config(get_config(arch)),
                               dtype="float32")


def run_local(arch: str) -> bool:
    """1-device TP mesh: real axis semantics without a multidevice runtime."""
    from repro.core.policy import PAPER_DEFAULT
    from repro.core.tp import TPContext
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("model",))
    ctx = TPContext(mesh=mesh, data_axes=(), policy=PAPER_DEFAULT)
    print("== jaxpr audit: 1-device TP mesh, policy "
          f"{PAPER_DEFAULT.describe()} ==\n")
    return audit_matrix(arch, mesh, ctx)


def run_tp_subprocess(arch: str) -> bool:
    """Re-run the audit on an 8-host-device data(2)×model(4) mesh — the
    gathered byte counts and axis sizes the paper's tables are about."""
    script = (
        "import sys; sys.path.insert(0, 'src')\n"
        "from scripts.static_audit import audit_matrix\n"
        "from repro.launch.mesh import make_mesh\n"
        "from repro.launch.sharding import make_context\n"
        "from repro.core.policy import PAPER_DEFAULT\n"
        "mesh = make_mesh((2, 4), ('data', 'model'))\n"
        "ctx = make_context(mesh, None, policy=PAPER_DEFAULT)\n"
        f"ok = audit_matrix({arch!r}, mesh, ctx)\n"
        "sys.exit(0 if ok else 1)\n"
    )
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    print("== jaxpr audit: subprocess data(2) x model(4) mesh ==\n",
          flush=True)
    proc = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                          capture_output=True, text=True)
    sys.stdout.write(proc.stdout)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-4000:])
    return proc.returncode == 0


def run_kv_subprocess(arch: str) -> bool:
    """The full engine matrix again with sequence-sharded pools on a
    kv(2)×data(2)×model(2) mesh: every traced step program carries
    ``kv_shards=2``, so the ``pool-reshard`` rule is armed on every row
    (sharded +pallas and the gated-compressed variants included) and must
    stay green — the block exchange moves table-sized operands only, never
    a full-capacity replication."""
    script = (
        "import sys; sys.path.insert(0, 'src')\n"
        "from scripts.static_audit import audit_matrix\n"
        "from repro.launch.mesh import make_mesh\n"
        "from repro.launch.sharding import make_context\n"
        "from repro.core.policy import PAPER_DEFAULT\n"
        "mesh = make_mesh((2, 2, 2), ('kv', 'data', 'model'))\n"
        "ctx = make_context(mesh, None, policy=PAPER_DEFAULT, kv_axis='kv')\n"
        f"ok = audit_matrix({arch!r}, mesh, ctx)\n"
        "sys.exit(0 if ok else 1)\n"
    )
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    print("== jaxpr audit: subprocess kv(2) x data(2) x model(2) mesh "
          "(sequence-sharded pools) ==\n", flush=True)
    proc = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                          capture_output=True, text=True)
    sys.stdout.write(proc.stdout)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-4000:])
    return proc.returncode == 0


def run_lint() -> bool:
    from repro.staticcheck import lint_paths

    violations = lint_paths([ROOT / "src" / "repro", ROOT / "scripts"])
    print(f"== lint (SC001-SC006): {len(violations)} violations ==")
    for v in violations:
        print(f"  {v}")
    return not violations


def run_static_args() -> bool:
    from repro.staticcheck import jaxpr_audit

    findings = jaxpr_audit.audit_static_args([ROOT / "src" / "repro"])
    print(f"== jit static-arg audit: {len(findings)} findings ==")
    for f in findings:
        print(f"  {f}")
    return not findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="internlm2-1.8b",
                    help="config to build audit engines from (reduced)")
    ap.add_argument("--tp-mesh", action="store_true",
                    help="also audit on an 8-device data x model mesh "
                         "(subprocess with forced host devices)")
    ap.add_argument("--skip-lint", action="store_true",
                    help="jaxpr audit only")
    args = ap.parse_args(argv)

    ok = run_local(args.arch)
    if args.tp_mesh:
        ok &= run_tp_subprocess(args.arch)
        ok &= run_kv_subprocess(args.arch)
    if not args.skip_lint:
        ok &= run_lint()
        ok &= run_static_args()
    print(f"\nstatic audit: {'OK' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
