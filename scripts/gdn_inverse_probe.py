"""The gated delta rule alone (``parallel.gated_delta_rule``), one layer at
``qwen3-next-80b-a3b.stream.x1``'s size (1 x 16,384 positions, 16 key heads
read by 32 value heads, heads of 128, chunks of 64, bfloat16 operands), on
whatever device jax finds, by how the inverse ``T = (I + A)^{-1}`` of a
chunk's strictly lower-triangular system is made:

- ``product_highest`` / ``product_high`` / ``product_default``: ``A^64 = 0``,
  so ``T = (I - A)(I + A^2)(I + A^4) ... (I + A^32)``, five squarings and
  five products of ``[64, 64]``, at each matmul precision, with the
  derivative ``dT = -T dA T`` as a rule of its own (jax's own derivative of
  the product keeps its powers);
- ``solve_triangular``: XLA's triangular solve of ``I + A`` against the
  identity, which is what ``parallel/deltanet.py`` ``_unit_lower_inverse``
  does;
- ``substitution``: forward substitution a row at a time in a ``fori_loop``.

Prints one JSON line a variant (ms forward, ms forward and backward, each the
mean of ``--calls`` after a first call that compiles, and the largest gap of
the output and of a gradient from the first variant's, over the largest
value) and appends it to ``chiprun_out/gdn_inverse_probe.jsonl``; no cell
runs this file. It times ONE layer's rule with nothing beside it: the step's
share is the traced cell's ``gdn_chunk_ms_per_step`` and
``gdn_state_ms_per_step``.

It is what the choice of ``_unit_lower_inverse`` was made from, kept so that
it can be read again after a compiler or jax upgrade. The numbers in
``PERF.md`` section 6 (PR 45) are from the chip tool's calls of PR 45 (one
TPU v5e chip). A number from a CPU run of this file is no device number.

    python3 scripts/gdn_inverse_probe.py [--calls 5] [variant ...]
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
from jax import lax  # noqa: E402

from torchmpi_tpu.parallel import deltanet  # noqa: E402

# positions, key heads, value heads a key head, dk, dv, the chunk
T, KEY_HEADS, PER_KEY, DK, DV, CHUNK = 16384, 16, 2, 128, 128, 64


def inputs(seed):
    """What the mixer hands the rule: unit keys, unit queries over
    ``sqrt(dk)``, decays that last 1 to 1,000 positions, ``beta`` about a
    half."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    heads = KEY_HEADS * PER_KEY

    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = (unit(jax.random.normal(ks[0], (1, T, KEY_HEADS, DK)))
         / DK ** 0.5).astype(jnp.bfloat16)
    k = unit(jax.random.normal(ks[1], (1, T, KEY_HEADS, DK))).astype(
        jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, T, heads, DV))
    g = -jnp.exp(jax.random.uniform(
        ks[3], (1, T, heads), minval=-7.0, maxval=0.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, T, heads)))
    return q, k, v, g, beta


def product(precision):
    """The inverse as the product that ``a^n = 0`` allows."""
    def dot(x, y):
        return jnp.matmul(x, y, precision=precision)

    @jax.custom_vjp
    def inverse(a):
        n = a.shape[-1]
        out = jnp.eye(n, dtype=a.dtype) - a
        power, reach = a, 2
        while reach < n:
            power = dot(power, power)
            out = out + dot(out, power)
            reach *= 2
        return out

    def fwd(a):
        out = inverse(a)
        return out, out

    def bwd(out, g):
        back = jnp.swapaxes(out, -1, -2)
        return (-dot(dot(back, g), back),)

    inverse.defvjp(fwd, bwd)
    return inverse


def substitution(a):
    """Row ``i`` of ``T`` is ``e_i - a_i T``, a row at a time."""
    n = a.shape[-1]

    def row(i, t):
        new = -jnp.einsum(
            "...j,...jk->...k", lax.dynamic_index_in_dim(a, i, -2, False), t,
            precision=lax.Precision.HIGHEST)
        return lax.dynamic_update_index_in_dim(
            t, new + jax.nn.one_hot(i, n, dtype=a.dtype), i, -2)

    return lax.fori_loop(0, n, row, jnp.zeros_like(a))


VARIANTS = {
    "product_highest": product(lax.Precision.HIGHEST),
    "product_high": product(lax.Precision.HIGH),
    "product_default": product(lax.Precision.DEFAULT),
    "solve_triangular": deltanet._unit_lower_inverse,
    "substitution": substitution,
}


def rule(*args):
    return deltanet.gated_delta_rule(*args, chunk=CHUNK, dtype=jnp.bfloat16)


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
    ap.add_argument("variants", nargs="*", default=list(VARIANTS))
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    given = inputs(args.seed)
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    first, programs_own = None, deltanet._unit_lower_inverse
    with open(out_dir / "gdn_inverse_probe.jsonl", "a") as out:
        for name in args.variants:
            # the arrays go in as arguments; the rule reads the module's name
            deltanet._unit_lower_inverse = VARIANTS[name]
            # a function of its own a variant: jax.jit keeps one program a
            # function object, and would hand the first variant's to the rest
            forward = lambda *a: rule(*a)  # noqa: E731
            loss = lambda *a: jnp.sum(jnp.square(rule(*a)))  # noqa: E731
            try:
                o, fwd_ms = timed(jax.jit(forward), given, args.calls)
                (_, grads), both_ms = timed(
                    jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))),
                    given, args.calls)
                if first is None:
                    first = (o, grads)
                line = {
                    "variant": name, "fwd_ms": round(fwd_ms, 3),
                    "fwd_bwd_ms": round(both_ms, 3),
                    "out_gap_vs_first": gap(o, first[0]),
                    "grad_gap_vs_first": max(
                        gap(a, b) for a, b in zip(grads, first[1])),
                }
            except Exception as e:  # noqa: BLE001 - a variant may not fit
                line = {"variant": name, "error": repr(e)[:300]}
            finally:
                deltanet._unit_lower_inverse = programs_own
            line["device"] = device.device_kind
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
