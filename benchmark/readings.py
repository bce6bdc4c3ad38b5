"""Readings for the limits of ``correct``: the numbers compared, for the
program against the plain reference and for the control against it, over
seeds, in one process and at the cell's own size.

    python3 benchmark/readings.py --workload <cell> --seeds 1,2,3 --control 3

The control is the reference computed with fp8 operands (the nearest
precision under the configurations' bfloat16) and put in the program's
place; it is read on the first ``--control`` seeds. Training's readings
need no measured window. ``PERF.md`` records what the limits were set from.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import run as bench

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = bench.find_cell(spec, args.workload)
    jax, devices = bench.bring_up(cell["chips"], args.rehearse)
    if jax is None:
        return 2

    import torchmpi_tpu as mpi

    from benchmark import check, configs, traffic

    ledger = check.CompileLedger()
    cfg = configs.load(cell["config"], rehearse=args.rehearse)
    built = configs.build(cell["config"], cfg)
    mpi.start(devices=devices)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        mode = traffic.make(
            cell["traffic"], cfg, built, cell["chips"], seed, ledger,
            rehearse=args.rehearse)
        mode.first_steps()
        followed = mode.followed
        mode.release()
        batches = followed.pop("batches")
        params = built.make_state(seed)[0]
        ref = check.follow_reference(
            cell["config"], cfg, params, mode, batches)
        out = {"seed": seed, "program": spread_of(followed, ref)}
        if i < args.control:
            out["control"] = spread_of(check.follow_reference(
                cell["config"], cfg, params, mode, batches, "fp8"), ref)
        del params
        print("READING " + json.dumps(out), flush=True)
    mpi.stop()
    return 0


def spread_of(got, ref) -> dict:
    """Every number that can be compared, and each tree's ninth decile."""
    import numpy as np

    from benchmark import check

    out = {k: v[0] for k, v in check.compare(got, ref).items()}
    for name, tree in check.TREES.items():
        if ref.get(tree) is not None:
            gaps, _ = check.leaf_gaps(got[tree], ref[tree])
            out[name + "_p90"] = float(np.quantile(gaps, 0.9))
    return {k: float(f"{v:.4g}") for k, v in out.items()}


if __name__ == "__main__":
    sys.exit(main())
