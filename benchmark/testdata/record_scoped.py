"""Records ``scoped.xplane.pb``: on the chips of this machine, six steps of
the program's own engine (``AllReduceSGDEngine.train``, replicated over all
chips, the flat in-graph gradient sync of 67 M float32 values) on a
four-layer MLP of width 4096, fed by an
iterator that sleeps 40 ms before each batch. So the trace holds a step
with the scopes ``tm.fwd_bwd``, ``tm.grad_sync/pack``, ``/reduce``,
``/unpack`` and ``tm.optimizer``, and the program's ring holds an
``engine.input_wait`` span over every sleep, during which the chips idle.
Run on the chip; writes beside itself (or into the directory given) the
trace, the program's spans with the trace's origin (``scoped.spans.json``)
and what the self-test may expect.

    python3 benchmark/testdata/record_scoped.py [out_dir]
"""

import glob
import json
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import torchmpi_tpu as mpi
from torchmpi_tpu import telemetry
from torchmpi_tpu.engine import AllReduceSGDEngine

WIDTH, LAYERS, PER_CHIP, STEPS, SLEEP_S = 4096, 4, 512, 6, 0.04

out = Path(sys.argv[1] if len(sys.argv) > 1 else Path(__file__).parent)
out.mkdir(parents=True, exist_ok=True)
devices = jax.devices()
mpi.start(devices=devices)


def loss_fn(params, batch):
    x, y = batch
    for w, b in params:
        x = jnp.tanh(x @ w + b)
    return jnp.mean((x - y) ** 2)


rng = np.random.RandomState(0)
params = [
    (jnp.asarray(rng.randn(WIDTH, WIDTH) / 64, jnp.float32),
     jnp.zeros(WIDTH, jnp.float32))
    for _ in range(LAYERS)
]
# no broadcast: every chip starts from the same host arrays, and the trace
# then holds the step's program alone
engine = AllReduceSGDEngine(loss_fn, params, optimizer=optax.adam(1e-3),
                            broadcast_parameters=False)
del params
n = PER_CHIP * len(devices)
x = jax.device_put(
    jnp.asarray(rng.randn(n, WIDTH), jnp.float32), engine.batch_sharding)


def epoch():
    for _ in range(STEPS):
        time.sleep(SLEEP_S)
        yield x, x


engine.train(epoch, max_epochs=1)  # builds the step, outside the trace
tmp = out / "_trace"
shutil.rmtree(tmp, ignore_errors=True)
options = jax.profiler.ProfileOptions()
options.python_tracer_level = 0
options.host_tracer_level = 0
options.start_timestamp_ns = origin = time.time_ns()
jax.profiler.start_trace(str(tmp), profiler_options=options)
telemetry.spans.reset()
engine.train(epoch, max_epochs=1)
jax.profiler.stop_trace()
found = glob.glob(str(tmp / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
shutil.copy(found, out / "scoped.xplane.pb")
shutil.rmtree(tmp, ignore_errors=True)
(out / "scoped.spans.json").write_text(json.dumps({
    "origin_ns": origin,
    "spans": [
        [r.name, r.start_ns, r.dur_ns, r.id, r.parent, r.step]
        for r in telemetry.spans.records()
    ],
}))
(out / "scoped.expect.json").write_text(json.dumps({
    "devices": len(devices), "steps": STEPS, "sleep_s": SLEEP_S,
    "per_chip": PER_CHIP, "weights": LAYERS * WIDTH * WIDTH,
    "sync_bytes": 4 * LAYERS * (WIDTH * WIDTH + WIDTH),
    "sync_bytes_gauge": telemetry.metrics.gauge(
        "tm_engine_sync_bytes_per_step").value(),
}, indent=1))
print("recorded", out / "scoped.xplane.pb",
      (out / "scoped.xplane.pb").stat().st_size, "bytes")
mpi.stop()
