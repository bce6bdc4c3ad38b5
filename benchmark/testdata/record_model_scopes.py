"""Records ``model_scopes.xplane.pb``: on one chip of this machine, two
steps of the program's own engine over a small ``models.MoEDecoder`` (width
512, one full-attention layer and one window layer, 8 query to 2 KV heads of
64 with a norm on each query and key head, a window of 256 over sequences of
1,024 in blocks of 256, 4 of 16 experts of width 256 held at 4 a token, each
block recomputed in backward). So the trace holds a step whose forward,
recomputation and backward carry, under ``tm.fwd_bwd``, the scopes a
language model opens for its parts (``tm.lm.embed``, ``tm.lm.norm``,
``tm.attn.proj``, ``tm.moe.router``, ``tm.lm.head``, ``tm.lm.loss``) beside
the attention's and the expert layer's, and XLA's ``ragged-dot`` kernels,
which bear no ``op_name``. Run on the chip; writes beside itself (or into
the directory given) the trace and how it was made.

    python3 benchmark/testdata/record_model_scopes.py [out_dir]
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
from torchmpi_tpu.engine import AllReduceSGDEngine
from torchmpi_tpu.models import (
    MoEDecoder,
    init_lm_params,
    init_moe_state,
    make_moe_lm_loss_fn,
)

SEQ, BATCH, STEPS, LAYERS, WINDOW, BLOCK = 1024, 4, 2, 2, 256, 256

out = Path(sys.argv[1] if len(sys.argv) > 1 else Path(__file__).parent)
out.mkdir(parents=True, exist_ok=True)
mpi.start(devices=jax.devices()[:1])
model = MoEDecoder(
    vocab_size=2048, num_layers=LAYERS, d_model=512, num_heads=8,
    num_kv_heads=2, head_dim=64, expert_width=256, num_experts=16, top_k=4,
    held=(0, 1, 2, 3), window=WINDOW, window_layout=(0, 1),
    rope_layout=(0, 1), attn_block=BLOCK, qk_norm=True, remat=True,
    dtype=jnp.bfloat16)
engine = AllReduceSGDEngine(
    make_moe_lm_loss_fn(model), init_lm_params(model, SEQ),
    optimizer=optax.adamw(1e-3), model_state=init_moe_state(model),
    broadcast_parameters=False)
toks = np.random.default_rng(0).integers(
    0, 2048, size=(BATCH, SEQ + 1), dtype=np.int32)
batch = (toks[:, :-1], toks[:, 1:])


def epoch():
    for _ in range(STEPS):
        yield batch


engine.train(epoch, max_epochs=1)  # builds the step, outside the trace
tmp = out / "_trace"
shutil.rmtree(tmp, ignore_errors=True)
options = jax.profiler.ProfileOptions()
options.python_tracer_level = 0
options.host_tracer_level = 0
options.start_timestamp_ns = time.time_ns()
jax.profiler.start_trace(str(tmp), profiler_options=options)
engine.train(epoch, max_epochs=1)
jax.profiler.stop_trace()
found = glob.glob(str(tmp / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
shutil.copy(found, out / "model_scopes.xplane.pb")
shutil.rmtree(tmp, ignore_errors=True)
(out / "model_scopes.expect.json").write_text(json.dumps({
    "steps": STEPS, "layers": LAYERS, "seq": SEQ, "window": WINDOW,
    "block": BLOCK, "device_kind": jax.devices()[0].device_kind,
    "jax": jax.__version__,
}, indent=1))
print("recorded", out / "model_scopes.xplane.pb",
      (out / "model_scopes.xplane.pb").stat().st_size, "bytes")
mpi.stop()
