"""Records ``decoder.xplane.pb``: on one chip of this machine, two steps of
the program's own engine over a small ``models.MoEDecoder`` (width 512, one
full-attention layer and one window layer, 8 query to 2 KV heads of 64, a
window of 256 over sequences of 1,024 in blocks of 256, 4 of 16 experts of
width 256 held at 4 a token, each block recomputed in backward; two layers
because the trace's size is that of the program's operation metadata). So the
trace holds a step whose forward, recomputation and backward carry the
scopes ``tm.attn.full``, ``tm.attn.window``, ``tm.moe.route``,
``tm.moe.experts`` and ``tm.moe.combine`` under ``tm.fwd_bwd``. Run on the
chip; writes beside itself (or into the directory given) the trace and
what the self-test may expect.

    python3 benchmark/testdata/record_decoder.py [out_dir]
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
    rope_layout=(0, 1), attn_block=BLOCK, remat=True,
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
shutil.copy(found, out / "decoder.xplane.pb")
shutil.rmtree(tmp, ignore_errors=True)
(out / "decoder.expect.json").write_text(json.dumps({
    "steps": STEPS, "layers": LAYERS, "seq": SEQ, "window": WINDOW,
    "block": BLOCK,
}, indent=1))
print("recorded", out / "decoder.xplane.pb",
      (out / "decoder.xplane.pb").stat().st_size, "bytes")
mpi.stop()
