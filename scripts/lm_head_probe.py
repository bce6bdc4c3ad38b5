"""The vocabulary head and its loss alone, on whatever device jax finds: the
loss and its three gradients whole (``jax.value_and_grad`` of
``lm_cross_entropy(scale * (x @ W + b), targets)``, the ``[rows, V]``
float32 logits and their gradient as arrays) against
``models.lm_head.blocked_head_loss`` by block size, at the ``(rows, V, D)``
of the benchmark's language-model cells. Prints one JSON line a cell (ms a
call, the median of ``--calls``, and the TFLOP/s of a product, ``3 x 2 x
rows x D x V`` over the time: the elementwise passes are in the time, so a
product alone is faster than this says) and appends it to
``chiprun_out/lm_head_probe.jsonl``; no cell runs this file.

With ``--products`` it times one block's three products apart, each in the
walk's own form, at a cell's ``[block, D] x [D, V]``: what the forward
product's rate is, and whether a gradient product falls short of it.

It is what ``models.lm_head.LOGITS_BLOCK_BYTES`` was set from, kept so that
it can be read again after a compiler or jax upgrade. The numbers in
``PERF.md`` section 6 (PR 43) are from the chip tool's calls of PR 43 (one
TPU v5e chip), and ``PERF.md`` says which call gave which. A number from a
CPU run of this file is no device number.

    python3 scripts/lm_head_probe.py [--calls 5] [--blocks 2048 4096] [cell ...]
    python3 scripts/lm_head_probe.py --products [cell ...]
"""

import argparse
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from torchmpi_tpu.models import lm_head  # noqa: E402
from torchmpi_tpu.models.lm import lm_cross_entropy  # noqa: E402

# per_chip_batch x sequence_length, the vocabulary held, the width, a bias,
# the scale on the logits; the last norm hands every head float32 rows
CELLS = {
    "falcon-h1-34b": (16384, 32640, 5120, False, 0.0078125),
    "brumby-14b": (32768, 18992, 5120, False, 1.0),
    "smallthinker-21b-a3b": (16384, 18992, 2560, False, 1.0),
    "laguna-s-2-1": (16384, 12544, 3072, False, 1.0),
    "keye-vl-2-30b-a3b": (16384, 18992, 2048, False, 1.0),
    "gpt2-medium": (8192, 50257, 1024, True, 1.0),
}
BLOCKS = (1024, 2048, 4096, 8192)


def value_and_grads(loss, x, kernel, bias):
    """``loss(x, kernel, bias)`` with its gradients by each that is there."""
    return jax.value_and_grad(loss, (0, 1) if bias is None else (0, 1, 2))(
        x, kernel, bias)


def whole(x, kernel, bias, scale, targets):
    """The head and the loss as the models spelled them before PR 43."""
    def loss(x, kernel, bias):
        logits = x @ kernel
        if bias is not None:
            logits = logits + bias
        return lm_cross_entropy(scale * logits, targets)

    return value_and_grads(loss, x, kernel, bias)


def blocked(x, kernel, bias, scale, targets, block):
    return value_and_grads(
        lambda *given: lm_head.blocked_head_loss(
            *given, scale, targets, block), x, kernel, bias)


def time_ms(fn, args, calls):
    jax.block_until_ready(fn(*args))  # builds
    jax.block_until_ready(fn(*args))
    read = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        read.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(read)


def inputs(cell, seed):
    rows, V, D, with_bias, scale = CELLS[cell]
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((rows, D), np.float32))
    kernel = jnp.asarray(rng.standard_normal((D, V), np.float32) * 0.02)
    bias = jnp.zeros((V,), jnp.float32) if with_bias else None
    targets = jnp.asarray(rng.integers(0, V, rows, dtype=np.int32))
    return x, kernel, bias, scale, targets


def products(cell, block, calls, seed):
    """A block's three products apart, as ``lm_head._visit`` writes them."""
    rows, V, D, _, _ = CELLS[cell]
    block = min(block, rows)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((block, D), np.float32))
    kernel = jnp.asarray(rng.standard_normal((D, V), np.float32) * 0.02)
    d = jnp.asarray(rng.standard_normal((block, V), np.float32))
    dot = lambda dims: lambda a, b: lax.dot_general(  # noqa: E731
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32)
    forms = {
        "logits = x @ W": (dot(((1,), (0,))), (x, kernel)),
        "dx = d @ W^T": (dot(((1,), (1,))), (d, kernel)),
        "dW = x^T @ d": (dot(((0,), (0,))), (x, d)),
    }
    flop = 2 * block * D * V
    ms = {k: time_ms(jax.jit(fn), args, calls)
          for k, (fn, args) in forms.items()}
    return {"cell": cell, "block": block, "V": V, "D": D,
            "ms": {k: round(v, 3) for k, v in ms.items()},
            "tflops": {k: round(flop / v / 1e9, 1) for k, v in ms.items()}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*", default=list(CELLS))
    ap.add_argument("--products", action="store_true",
                    help="one block's three products apart")
    ap.add_argument("--blocks", type=int, nargs="*", default=list(BLOCKS))
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    with open(out_dir / "lm_head_probe.jsonl", "a") as out:
        for cell in args.cells:
            rows, V, D, _, _ = CELLS[cell]
            rule = lm_head.block_rows(rows, V)
            if args.products:
                line = products(cell, rule, args.calls, args.seed)
            else:
                given = inputs(cell, args.seed)
                sizes = sorted({b for b in args.blocks if b < rows}
                               | {rule, rows})
                ms = {"whole": time_ms(jax.jit(whole), given, args.calls)}
                for block in sizes:
                    ms[f"block_{block}"] = time_ms(
                        jax.jit(blocked, static_argnums=(3, 5)),
                        given + (block,), args.calls)
                flop = 3 * 2 * rows * D * V
                line = {
                    "cell": cell, "rows": rows, "V": V, "D": D,
                    "block_rows": rule,
                    "ms": {k: round(v, 3) for k, v in ms.items()},
                    "tflops_a_product": {
                        k: round(flop / v / 1e9, 1) for k, v in ms.items()},
                }
            line["device"] = device.device_kind
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
