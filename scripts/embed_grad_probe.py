"""The embedding's gradient alone, on whatever device jax finds: jax's own
transpose of the gather (``zeros.at[ids].add(g)``, XLA's scatter-add)
against ``models.embedding.sorted_embedding_grad``, at the
``(T, V, D, dtype)`` of the benchmark's language-model cells, with uniform
ids, with the cells' Zipf(1) ids and (where ``T <= V``) with no id twice.
Prints one JSON line a reading (ms a call, the median of ``--calls``) and
appends it to ``chiprun_out/embed_grad_probe.jsonl``; no cell runs this
file.

With ``--widths`` it walks a grid of table shapes around the cells' with
the cells' Zipf ids instead: where XLA's scatter-add turns slow (its time
follows the table's rows and turns on the rows' width).

It is what ``models.embedding.BLOCK`` and ``takes_sorted_sum`` were set
from, kept so that they can be read again after a compiler or jax upgrade.
The numbers in ``PERF.md`` section 6 (PR 42) are from the chip tool's calls
of PR 42 (one TPU v5e chip): the cells' table from the second, ``chiprun
--chips 1 --timeout 3550 -- bash scratch/call2.sh``, whose first command was
``python3 scripts/embed_grad_probe.py --stages``; the widths' table from
the fourth, which ran this file's ``WIDTHS`` grid from an uncommitted copy
(``scratch/probe_boundary.py``); the first call ran a wider set of variants
of the sorted sum from an uncommitted file, and ``PERF.md`` says which. A
number from a CPU run of this file is no device number.

    python3 scripts/embed_grad_probe.py [--stages] [--calls 10] [cell ...]
    python3 scripts/embed_grad_probe.py --widths
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

from torchmpi_tpu.models import embedding  # noqa: E402

# per_chip_batch x sequence_length, the table's rows and columns, the dtype
# of the cotangent that reaches the gather, the cell's own ids
CELLS = {
    "falcon-h1-34b": (16384, 32640, 5120, jnp.float32, "zipf"),
    "brumby-14b": (32768, 18992, 5120, jnp.float32, "zipf"),
    "smallthinker-21b-a3b": (16384, 18992, 2560, jnp.bfloat16, "zipf"),
    "laguna-s-2-1": (16384, 12544, 3072, jnp.bfloat16, "zipf"),
    "keye-vl-2-30b-a3b": (16384, 18992, 2048, jnp.bfloat16, "zipf"),
    "gpt2-medium": (8192, 50257, 1024, jnp.bfloat16, "uniform"),
}


# (T, V, D, dtype of the cotangent) around the cells': one vocabulary at
# five widths, two more vocabularies at the widths between, tables of one
# size at other shapes, half and twice the tokens, float32 rows
WIDTHS = [
    (16384, 18992, 2048, "bfloat16"), (16384, 18992, 2176, "bfloat16"),
    (16384, 18992, 2304, "bfloat16"), (16384, 18992, 2432, "bfloat16"),
    (16384, 18992, 2560, "bfloat16"), (16384, 12544, 3072, "bfloat16"),
    (16384, 12544, 3584, "bfloat16"), (16384, 12544, 4096, "bfloat16"),
    (16384, 16384, 2560, "bfloat16"), (16384, 16384, 3072, "bfloat16"),
    (16384, 25088, 2048, "bfloat16"), (16384, 9496, 2560, "bfloat16"),
    (16384, 50257, 1024, "bfloat16"), (16384, 37888, 1024, "bfloat16"),
    (8192, 18992, 2560, "bfloat16"), (32768, 18992, 2048, "bfloat16"),
    (16384, 18992, 1024, "float32"), (16384, 18992, 1280, "float32"),
    (16384, 18992, 2560, "float32"), (16384, 9496, 5120, "float32"),
]


def make_ids(kind, rng, T, V):
    if kind == "uniform":
        return rng.integers(0, V, size=T, dtype=np.int32)
    if kind == "distinct":
        return rng.permutation(V)[:T].astype(np.int32)
    # the cells' generator (benchmark/configs/falcon-h1-34b.py make_data)
    cdf = np.cumsum(1.0 / np.arange(1, V + 1))
    return np.searchsorted(
        cdf / cdf[-1], rng.random(T), side="right"
    ).clip(max=V - 1).astype(np.int32)


def scatter_add(g, ids, V):
    """What jax's transpose of ``table.astype(dtype)[ids]`` does: the rows
    added in the cotangent's dtype, the table cast after."""
    return jnp.zeros((V, g.shape[1]), g.dtype).at[ids].add(g).astype(
        jnp.float32)


def variants(V, stages):
    """name -> function of ``(g, ids)``."""
    out = {
        "scatter_add": lambda g, ids: scatter_add(g, ids, V),
        "sorted_256": lambda g, ids: embedding.sorted_embedding_grad(
            g, ids, V, 256),
        "sorted_512": lambda g, ids: embedding.sorted_embedding_grad(
            g, ids, V, 512),
    }
    if stages:
        # the sorted sum's three stages apart, each fed the one before's
        # results as arguments
        out["stage.by_id"] = lambda g, ids: embedding._by_id(g, ids)
        out["stage.sums_by_rank"] = lambda g, ids, by_id: (
            embedding._sums_by_rank(by_id[0], by_id[3], embedding.BLOCK))
        out["stage.to_table"] = lambda g, ids, by_id, sums: (
            embedding._to_table(sums, *by_id[1:], V))
    return out


def time_ms(fn, args, calls):
    jax.block_until_ready(fn(*args))  # builds
    jax.block_until_ready(fn(*args))
    read = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        read.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(read)


def widths(out, device, calls, seed):
    """One line a shape of ``WIDTHS``: XLA's scatter-add, the sorted sum,
    what the rule takes there."""
    rng = np.random.default_rng(seed)
    for T, V, D, name in WIDTHS:
        dtype = jnp.dtype(name)
        g = jnp.asarray(rng.standard_normal((T, D), np.float32), dtype)
        ids = jnp.asarray(make_ids("zipf", rng, T, V))
        line = {
            "T": T, "V": V, "D": D, "dtype": name,
            "device": device.device_kind,
            "takes_sorted_sum": embedding.takes_sorted_sum(
                D, dtype.itemsize),
            "ms": {k: round(time_ms(jax.jit(fn), (g, ids), calls), 3)
                   for k, fn in list(variants(V, False).items())[:2]},
        }
        line["scatter_add_us_a_table_row"] = round(
            line["ms"]["scatter_add"] * 1e3 / V, 3)
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*", default=list(CELLS))
    ap.add_argument("--stages", action="store_true",
                    help="time the sorted sum's three stages apart too")
    ap.add_argument("--widths", action="store_true",
                    help="the grid of table shapes, not the cells'")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    with open(out_dir / "embed_grad_probe.jsonl", "a") as out:
        if args.widths:
            widths(out, device, args.calls, args.seed)
            return 0
        for cell in args.cells:
            T, V, D, dtype, own = CELLS[cell]
            rng = np.random.default_rng(args.seed)
            g = jnp.asarray(rng.standard_normal((T, D), np.float32), dtype)
            for kind in ("uniform", "zipf") + (("distinct",) * (T <= V)):
                ids_np = make_ids(kind, rng, T, V)
                ids = jnp.asarray(ids_np)
                line = {
                    "cell": cell, "T": T, "V": V, "D": D,
                    "dtype": jnp.dtype(dtype).name, "ids": kind,
                    "cells_own_ids": kind == own,
                    "distinct": int(np.unique(ids_np).size),
                    "longest_run": int(np.bincount(ids_np).max()),
                    "device": device.device_kind, "ms": {},
                }
                fns = variants(V, args.stages and kind == own)
                by_id = sums = None
                if "stage.by_id" in fns:
                    by_id = jax.jit(fns["stage.by_id"])(g, ids)
                    sums = jax.jit(fns["stage.sums_by_rank"])(g, ids, by_id)
                for name, fn in fns.items():
                    extra = {"stage.sums_by_rank": (by_id,),
                             "stage.to_table": (by_id, sums)}.get(name, ())
                    line["ms"][name] = round(time_ms(
                        jax.jit(fn), (g, ids) + extra, args.calls), 3)
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
