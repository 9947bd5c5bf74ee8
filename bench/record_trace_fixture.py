"""Record the small chip trace that ``test_bench_trace.py`` reduces.

    python3 bench/record_trace_fixture.py <out_dir>

On a TPU: a jitted step (a matrix product and an all-reduce over the local
chips) runs five times with a host sleep between calls, under a profiler
trace; the trace's ``.xplane.pb`` is copied to
``<out_dir>/fixture.xplane.pb`` with the host-clock facts the test checks.
"""
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench.trace_reduce import find_trace  # noqa: E402


def main(out):
    out = Path(out)
    out.mkdir(parents=True, exist_ok=True)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit("record_trace_fixture: needs a TPU")
    mesh = jax.make_mesh((len(devs),), ("x",), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,))
    x = jax.device_put(jnp.ones((len(devs) * 1024, 1024), jnp.bfloat16),
                       NamedSharding(mesh, P("x")))
    step = jax.jit(lambda a: jnp.sum(a @ a.T.reshape(1024, -1)[:, :1024]))
    step(x).block_until_ready()
    tmp = out / "raw"
    jax.profiler.start_trace(str(tmp))
    t0 = time.perf_counter()
    for _ in range(5):
        with jax.profiler.TraceAnnotation("host_sleep"):
            time.sleep(0.02)
        step(x).block_until_ready()
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    shutil.copy(find_trace(tmp), out / "fixture.xplane.pb")
    shutil.rmtree(tmp)
    (out / "fixture.json").write_text(json.dumps(
        {"devices": len(devs), "kind": devs[0].device_kind, "calls": 5,
         "host_sleep_s": 0.1, "loop_wall_s": wall}))


if __name__ == "__main__":
    main(sys.argv[1])
