"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and everything that belongs to it by
name: ``configs/<config>.{json,py}``, ``reference/<config>.py``,
``traffic/<traffic>.json`` and the ``traffic/<mode>.py`` it names,
``end_to_end/<metric>.py``, ``layer_metrics/<metric>.py``. Prints, as the
last line of its output, one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device``. With no TPU, or fewer chips than the
cell asks for, it prints no result and exits 2. ``--rehearse`` drives every
path at tiny sizes on CPU devices and prints no number under a metric's name.
"""

import time

T0 = time.time()  # set-up is counted from here: before any heavy import

import argparse
import gc
import json
import math
import os
import re
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(
        f"run.py: no workload {name!r} in BENCHMARK.json (known: "
        f"{[c['name'] for c in spec['workloads']]})")


def metrics_of(spec: dict, group: str, cell: str) -> list:
    return [
        m for m in spec[group]
        if "workloads" not in m or cell in m["workloads"]
    ]


def bring_up(chips: int, rehearse: bool):
    """Import jax, place its compilation cache, and find the chips. Returns
    (jax, the devices to use), or (None, None) where there is no TPU or
    there are too few chips: then no result may be printed."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={chips}"
        ).strip()

    import jax

    if not rehearse:
        # the cache's path is part of its key: the environment's, or one
        # fixed place inside the checkout. Every program is kept, so that
        # a second run compiles nothing.
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update(
                "jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    platform = devices[0].platform
    if not rehearse and platform != "tpu":
        print(f"run.py: no TPU (jax reports platform {platform!r}); nothing "
              "was measured. --rehearse drives the paths on the CPU.",
              file=sys.stderr)
        return None, None
    if len(devices) < chips:
        print(f"run.py: the cell needs {chips} chips, jax reports "
              f"{len(devices)}", file=sys.stderr)
        return None, None
    return jax, devices[:chips]


def replica_divergence(engine) -> float:
    """The largest difference between any chip's copy of a parameter and
    the chips' mean, worked out on the chips."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    axis = engine.mesh.axis_names[0]

    def spread(params):
        worst = [
            jnp.max(jnp.abs(a - jax.lax.pmean(a, axis)))
            for a in jax.tree_util.tree_leaves(params)
        ]
        return jax.lax.pmax(jnp.max(jnp.stack(worst)), axis)

    fn = jax.jit(jax.shard_map(
        spread, mesh=engine.mesh, in_specs=P(), out_specs=P(),
        check_vma=False))
    return float(fn(engine.params))


def main(argv=None) -> int:
    args = parse(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = find_cell(spec, args.workload)
    chips = cell["chips"]
    seconds = args.seconds if args.seconds is not None \
        else spec["run_seconds"]
    jax, devices = bring_up(chips, args.rehearse)
    if jax is None:
        return 2
    platform, kind = devices[0].platform, devices[0].device_kind
    cache_dir = jax.config.jax_compilation_cache_dir

    import torchmpi_tpu as mpi

    from benchmark import check, configs, flops, traffic, xplane

    if not args.rehearse:
        flops.peak_flops(kind)  # an unknown chip is an error, and an early one
    ledger = check.CompileLedger()
    mpi.start(devices=devices)
    cfg = configs.load(cell["config"], rehearse=args.rehearse)
    built = configs.build(cell["config"], cfg)
    mode = traffic.make(cell["traffic"], cfg, built, chips, args.seed, ledger,
                        rehearse=args.rehearse)
    log(f"{args.workload}: seed {args.seed} on {chips} x {kind} "
        f"({platform}), per-chip batch {cfg['per_chip_batch']}, cache "
        f"{cache_dir}; built in {time.time() - T0:.1f} s")

    mode.first_steps()
    log(f"first dispatch {mode.first_step_s:.2f} s; followed losses "
        f"{mode.followed['losses']}")
    mode.warm_up()
    setup_s = time.time() - T0
    trace_dir = ROOT / ".bench_trace" / args.workload
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        phase = mode.traced(trace_dir)
    else:
        full_gcs = gc.get_stats()[2]["collections"]
        phase = mode.window(seconds)
        full_gcs = gc.get_stats()[2]["collections"] - full_gcs
    stats = [d.memory_stats() for d in devices]
    log(f"memory_stats of chip 0: {json.dumps(stats[0])}")
    # The fullest chip's peak. This runtime counts what the loaded programs
    # hold back for their own temporaries (the activations) under
    # "reserved", apart from what the allocator handed out, and keeps no
    # peak of the sum: so the larger of the handed-out peak and what is
    # handed out now plus the reserved peak.
    peak = None
    if all(s and "peak_bytes_in_use" in s for s in stats):
        peak = max(
            max(s["peak_bytes_in_use"],
                s["bytes_in_use"] + s.get("peak_bytes_reserved", 0))
            for s in stats)
    divergence = replica_divergence(mode.engine) if chips > 1 else 0.0
    followed, loss_at_seed = mode.followed, mode.loss_at_seed
    mode.release()
    mpi.stop()

    # -- correct: the plain reference follows the same first steps ------
    t_ref = time.time()
    params = built.make_state(args.seed)[0]
    ref = check.follow_reference(
        cell["config"], cfg, params, mode, followed.pop("batches"))
    del params
    numbers = check.compare(followed, ref)
    losses = phase["losses"]
    numbers["nonfinite_losses"] = float(
        sum(not math.isfinite(v) for v in losses))
    numbers["programs_built_in_window"] = float(phase["programs_in_window"])
    numbers["replica_divergence"] = divergence
    limits = {
        **cfg["limits"][mode.mix["mode"]], "nonfinite_losses": 0,
        "programs_built_in_window": 0, "replica_divergence": 0,
    }
    if built.loss_must_fall:
        # data with class structure: the loss ends under where it began
        numbers["loss_end_over_start"] = losses[-1] / loss_at_seed
        limits["loss_end_over_start"] = 1.0
    correct = check.verdict(numbers, limits, out=print)
    log(f"reference followed {len(ref['losses'])} losses in "
        f"{time.time() - t_ref:.1f} s (not in setup_s)")

    # -- metrics -------------------------------------------------------
    device = {"platform": platform, "kind": kind, "count": chips,
              "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": int(phase["steps"]),
            "failed": int(numbers["nonfinite_losses"])}
    values = {}
    if args.trace:
        reduced = {
            k: xplane.reduce(xplane.find(p), phase["spans"], origin)
            for k, (p, origin) in phase["traces"].items()
        }
        run = {
            "phase": phase, "first_step_s": mode.first_step_s, "cfg": cfg,
            "memory_peak_bytes": peak, "chips": chips,
            "steady": reduced["steady"], "boundary": reduced["boundary"],
        }
        for m in metrics_of(spec, "per_layer", args.workload):
            value = configs.load_module(
                HERE / "layer_metrics" / f"{m['name']}.py").read(run)
            if value is not None:
                values[m["name"]] = {"value": value, "unit": m["unit"]}
        steady = reduced["steady"]
        if steady.get("devices"):
            device["busy_s"] = steady["busy_s"]
            device["window_s"] = steady["window_s"]
            line["breakdown"] = xplane.breakdown(steady)
            edge = xplane.breakdown(reduced["boundary"])["idle_gaps"]
            log(f"idle gaps of the boundary trace: {json.dumps(edge)}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        log(f"candidates samples_per_s_per_chip total_over_total="
            f"{phase['end_to_end']['samples_per_s_per_chip']!r} "
            f"median_of_chunks="
            f"{phase['rate_median']!r} steps={phase['steps']} "
            f"window_s={phase['time']!r}")
        if "slowest" in phase:
            log(f"slowest chunks (over the warm-up's median, index): "
                f"{phase['slowest']}; full garbage collections in the "
                f"window: {full_gcs}")
        run = {
            "phase": phase, "setup_s": setup_s, "chips": chips, "cfg": cfg,
            "flops_per_sample": built.flops_per_sample,
            "peak_flops": None if args.rehearse else flops.peak_flops(kind),
        }
        for m in metrics_of(spec, "end_to_end", args.workload):
            value = configs.load_module(
                HERE / "end_to_end" / f"{m['name']}.py").read(run)
            if value is not None:
                values[m["name"]] = {"value": value, "unit": m["unit"]}
    if args.rehearse:
        # a CPU's numbers are never written under a device metric's name
        line["rehearsed"] = sorted(values)
        values = {}
    line["metrics"] = values
    line["device"] = device
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
