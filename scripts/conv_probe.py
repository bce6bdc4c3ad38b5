"""The short causal convolution and its SiLU alone, one layer's operation at
the two cells' shapes (``qwen3-next-80b-a3b.stream.x1``: ``[1, 16384, 8192]``
bfloat16, 4 taps, no bias; ``falcon-h1-34b.stream.x1``: ``[1, 16384, 1024]``
float32, 4 taps and a bias), on whatever device jax finds:

- ``xla``: ``jax.nn.silu(parallel.ssm.causal_conv1d(...))`` and jax's own
  derivative of it, as the models ran it before PR 46;
- ``kernels PxCxR``: ``ops/conv_kernel.py``'s forward and backward kernels
  under tiles of ``P`` positions and ``C`` channels of which the inner loop
  holds ``R`` rows in registers at a time (``--tiles``; the module's own
  by default).

Prints one JSON line a shape and variant (ms forward, ms backward alone for
the kernels and forward with backward for ``xla``, each the mean of
``--calls`` after a call that compiles; the bytes a pass has to move over
the time as GB/s; the largest gap of ``y``, ``dx``, ``dtaps`` from ``xla``'s
over the largest value) and appends it to ``chiprun_out/conv_probe.jsonl``;
no cell runs this file. It times ONE layer's operation with nothing beside
it: the step's share is the traced cells' ``gdn_conv_ms_per_step`` and
``ssm_conv_ms_per_step``.

It is what the kernels' tile was chosen from, kept so that it can be read
again after a compiler or jax upgrade. The numbers in ``PERF.md`` section 6
(PR 46) are from the chip tool's calls of PR 46 (one TPU v5e chip). A number
from a CPU run of this file is no device number.

    python3 scripts/conv_probe.py [--calls 10] [--tiles 1024x512x16,512x512x32]
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from torchmpi_tpu.ops import conv_kernel  # noqa: E402
from torchmpi_tpu.parallel import ssm  # noqa: E402

# cell, positions, channels, the input's dtype, whether there is a bias
SHAPES = (
    ("qwen3-next-80b-a3b", 16384, 8192, jnp.bfloat16, False),
    ("falcon-h1-34b", 16384, 1024, jnp.float32, True),
)
TAPS = 4


def inputs(seed, t, c, dtype, biased):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    bound = TAPS ** -0.5
    bias = jax.random.uniform(ks[2], (c,), jnp.float32, -bound, bound)
    return (jax.random.normal(ks[0], (1, t, c)).astype(dtype),
            jax.random.uniform(ks[1], (TAPS, c), jnp.float32, -bound, bound),
            bias if biased else jnp.zeros_like(bias),
            jax.random.normal(ks[3], (1, t, c)))


def plain(x, taps, bias):
    return jax.nn.silu(ssm.causal_conv1d(x, taps, bias))


def timed(fn, args, calls):
    """``fn(*args)``'s result and ms a call, after a call that compiles."""
    out = jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, 1e3 * (time.perf_counter() - start) / calls


def gap(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--tiles", default=f"{conv_kernel.POSITIONS}x{conv_kernel.CHANNELS}"
        f"x{conv_kernel.ROWS}", help="comma-separated POSITIONSxCHANNELSxROWS")
    args = ap.parse_args(argv)
    tiles = [tuple(int(n) for n in tile.split("x"))
             for tile in args.tiles.split(",")]
    device = jax.devices()[0]
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    for cell, t, c, dtype, biased in SHAPES:
        operands = inputs(args.seed, t, c, dtype, biased)
        size = jnp.dtype(dtype).itemsize
        moved = {"fwd": t * c * (size + 4), "bwd": t * c * (2 * size + 4)}
        want, fwd_ms = timed(jax.jit(plain), operands[:3], args.calls)
        both = jax.jit(lambda x, taps, bias, dy: jax.vjp(
            plain, x, taps, bias)[1](dy))
        grads, both_ms = timed(both, operands, args.calls)
        lines = [{"variant": "xla", "fwd_ms": fwd_ms,
                  "fwd_and_bwd_ms": both_ms}]
        for positions, lanes, rows in tiles:
            if t % positions or c % lanes or rows * size % 32:
                continue  # no whole tiles, or rows that split a packed one
            tile = {"positions": positions, "lanes": lanes, "rows": rows}
            variant = f"kernels {positions}x{lanes}x{rows}"
            try:
                y, fwd_ms = timed(
                    lambda *a: conv_kernel.forward(*a, **tile), operands[:3],
                    args.calls)
                got, bwd_ms = timed(
                    lambda *a: conv_kernel.backward(*a, **tile), operands,
                    args.calls)
            except Exception as e:  # noqa: BLE001 - a tile Mosaic refuses
                lines.append({"variant": variant, "refused": str(e)[:300]})
                continue
            lines.append({
                "variant": variant, "fwd_ms": fwd_ms, "bwd_ms": bwd_ms,
                "fwd_GB_per_s": moved["fwd"] / fwd_ms / 1e6,
                "bwd_GB_per_s": moved["bwd"] / bwd_ms / 1e6,
                "y_gap": gap(y, want), "dx_gap": gap(got[0], grads[0]),
                "dtaps_gap": gap(got[1], grads[1]),
                "dbias_gap": gap(got[2], grads[2])})
        for line in lines:
            line = {"cell": cell, "shape": [1, t, c],
                    "dtype": jnp.dtype(dtype).name, **line,
                    "device": device.device_kind, "calls": args.calls}
            print(json.dumps(line), flush=True)
            with open(out_dir / "conv_probe.jsonl", "a") as f:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
