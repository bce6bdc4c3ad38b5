"""Records ``small.xplane.pb``: on the chips of this machine, six steps of
a matmul and a psum over all chips, the host asleep 20 ms before each step
under a ``bench.input_wait`` span and 100 ms between the third and the
fourth under ``bench.epoch_boundary``. Run on the chip; writes beside itself
(or into the directory given) the trace, the spans with the trace's origin
(``small.spans.json``) and what the self-test may expect.

    python3 benchmark/testdata/record_small.py [out_dir]
"""

import glob
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

out = Path(sys.argv[1] if len(sys.argv) > 1 else Path(__file__).parent)
out.mkdir(parents=True, exist_ok=True)
devices = jax.devices()
mesh = Mesh(devices, ("mpi",))


def step(x):
    y = jnp.tanh(x @ x)
    return jax.lax.psum(y, "mpi") / len(devices)


fn = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=P("mpi"),
                           out_specs=P("mpi"), check_vma=False))
x = jax.device_put(jnp.ones((len(devices) * 1024, 1024), jnp.bfloat16) * 1e-3,
                   NamedSharding(mesh, P("mpi")))
jax.block_until_ready(fn(x))
tmp = out / "_trace"
shutil.rmtree(tmp, ignore_errors=True)
options = jax.profiler.ProfileOptions()
options.python_tracer_level = 0
options.host_tracer_level = 0
options.start_timestamp_ns = origin = time.time_ns()
jax.profiler.start_trace(str(tmp), profiler_options=options)
spans = []


def span(name, seconds=None, call=None):
    t0 = time.time_ns()
    result = call() if call else time.sleep(seconds)
    spans.append((name, t0 * 1e-9, time.time_ns() * 1e-9))
    return result


for i in range(6):
    if i == 3:
        span("bench.epoch_boundary", 0.1)
    span("bench.input_wait", 0.02)
    x = span("bench.dispatch", call=lambda: fn(x))
    jax.block_until_ready(x)
jax.profiler.stop_trace()
found = glob.glob(str(tmp / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
shutil.copy(found, out / "small.xplane.pb")
shutil.rmtree(tmp, ignore_errors=True)
(out / "small.spans.json").write_text(
    json.dumps({"origin_ns": origin, "spans": spans}))
(out / "small.expect.json").write_text(json.dumps({
    "devices": len(devices), "steps": 6,
    "idle_input_wait_s": [0.08, 0.2], "boundary_idle_s": [0.1, 0.2],
}, indent=1))
print("recorded", out / "small.xplane.pb",
      (out / "small.xplane.pb").stat().st_size, "bytes")
