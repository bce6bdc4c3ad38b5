"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, the entry points a user calls, no arguments needed:

    python chip_smoke.py

It uses every local chip jax reports (1 or 4) and runs, in order:

1. *Device gate*: no TPU, no run — exit 2 with a one-line reason, nothing
   on stdout. ``--rehearse`` is the only way to run without a chip (tiny
   shapes on a 4-device CPU mesh, interpret-mode kernels, output marked
   ``"rehearsal": true``); it is never chosen automatically.
2. *Main path at full width*: ResNet-50 as published (3-4-6-3, 1000
   classes, 224 px, bf16 compute, SGD+momentum, learning rate scaled
   linearly from 0.1 at batch 256), 32 images per chip, through
   ``AllReduceSGDEngine(model_state=batch_stats)`` fed by
   ``data.InputPipeline`` via ``engine.train()``. Checked: every step's
   loss finite, the last epoch's mean loss below the first's, replicas
   bitwise equal and passing ``mpinn.check_with_allreduce``, no
   compilation after the first step, params and a batch shard on every
   chip. Reported: ``compile_s`` (the first optimizer step: trace,
   compile or cache load, one execution) and ``step_s`` (median of the
   later steps), every timing ending in ``block_until_ready``.
3. *Collectives on every chip* through the default selector route, each
   against numpy (skipped by name with one chip).
4. *Parallel layouts on real chips*: ``__graft_entry__.dryrun_multichip``
   (needs >= 4 chips).
5. *Kernels through the compiler*: every Pallas entry point with
   ``interpret=False`` at shapes a user would pass, against the XLA path.
   A kernel Mosaic refuses is listed ``"compiled": false`` with the
   compiler's message; one that compiles and disagrees fails the run.

The last two stdout lines are JSON: first the summary (device, versions,
a status per phase, ``"claim": null``), then the verdict the driver reads,
``{"ok": true, "device": {"platform", "kind", "count"}}`` and nothing
else. Any failed phase is an uncaught exception and a non-zero exit with
neither line. A watchdog turns a hang into a failure inside the 1200 s
the contract allows.
"""

from __future__ import annotations

import argparse
import faulthandler
import functools
import importlib.metadata
import json
import math
import os
import re
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

WATCHDOG_S = 1150
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLedger:
    """Counts what jax compiled (or loaded from the persistent cache) in
    this process, from jax.monitoring's own events."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == _BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1


def _mosaic_refusal(exc: BaseException):
    """The compiler's message when ``exc`` is Mosaic refusing a kernel;
    None for anything else (which must propagate)."""
    text = str(exc)
    if "Mosaic failed to compile" in text or "MosaicError" in type(exc).__name__:
        return text.strip().splitlines()[0][:400]
    return None


# ---------------------------------------------------------------------------
# phase 2: the engine's data-parallel training path
# ---------------------------------------------------------------------------


def phase_resnet(mpi, rehearse: bool, ledger: CompileLedger) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchmpi_tpu import nn as mpinn
    from torchmpi_tpu.data import InputPipeline
    from torchmpi_tpu.engine import AllReduceSGDEngine
    from torchmpi_tpu.models import (
        ResNet50,
        init_resnet,
        make_stateful_loss_fn,
    )
    from torchmpi_tpu.models.resnet import BottleneckBlock, ResNet
    from torchmpi_tpu.utils import synthetic_imagenet

    comm = mpi.current_communicator()
    p = comm.size
    if rehearse:
        image, classes, per_chip, dtype = 32, 10, 2, jnp.float32
        model = ResNet(
            stage_sizes=[1, 1, 1, 1], block=BottleneckBlock, num_filters=8,
            num_classes=classes, dtype=dtype,
        )
    else:
        image, classes, per_chip, dtype = 224, 1000, 32, jnp.bfloat16
        model = ResNet50(num_classes=classes, dtype=dtype)
    batches, epochs = 3, 4  # 12 optimizer steps; each batch is seen 4 times
    global_batch = per_chip * p
    params, stats = init_resnet(model, image)
    (x, y), _ = synthetic_imagenet(
        num_train=batches * global_batch, num_test=1, num_classes=classes,
        image_size=image, seed=4321,
    )

    steps = []  # (loss, seconds since the previous step ended, compiles so far)
    clock = {"t": None}

    def on_start(state):
        clock["t"] = time.perf_counter()

    def on_update(state):
        loss = float(jax.block_until_ready(state["loss"]))
        now = time.perf_counter()
        steps.append((loss, now - clock["t"], ledger.compiles))
        clock["t"] = now

    engine = AllReduceSGDEngine(
        make_stateful_loss_fn(model),
        params,
        # the published recipe's 0.1 is for batch 256: scale it linearly
        optimizer=optax.sgd(0.1 * global_batch / 256, momentum=0.9),
        model_state=stats,
        hooks={"on_start": on_start, "on_update": on_update},
    )
    pipe = InputPipeline(
        (x, y), batch_size=global_batch, num_ranks=p,
        sharding=engine.batch_sharding, seed=7,
        transform=lambda xb, yb: (xb.astype(dtype), yb),
    )
    compiles_before = ledger.compiles
    hits_before, compile_s_before = ledger.cache_hits, ledger.compile_s
    state = engine.train(pipe, max_epochs=epochs)

    losses = [s[0] for s in steps]
    assert len(losses) == batches * epochs, (len(losses), batches, epochs)
    assert all(math.isfinite(v) for v in losses), f"non-finite loss: {losses}"
    first, last = np.mean(losses[:batches]), np.mean(losses[-batches:])
    assert last < first, f"loss did not fall: epoch means {first} -> {last}"
    late_compiles = steps[-1][2] - steps[0][2]
    assert late_compiles == 0, f"{late_compiles} compilations after step 1"

    # each chip's own copy of every parameter, in rank order
    rank_of = {d: i for i, d in enumerate(comm.devices)}

    def replicas(a):
        shards = sorted(a.addressable_shards, key=lambda s: rank_of[s.device])
        assert len(shards) == p, (len(shards), p)
        return np.stack([np.asarray(s.data) for s in shards])

    stacked = jax.tree_util.tree_map(replicas, engine.params)
    divergence = max(
        float(np.abs(leaf - leaf[0]).astype(np.float32).max())
        for leaf in jax.tree_util.tree_leaves(stacked)
    )
    assert divergence == 0.0, f"replicas diverged by {divergence}"
    mpinn.check_with_allreduce(stacked, comm)

    param_bytes = sum(
        leaf[0].nbytes for leaf in jax.tree_util.tree_leaves(stacked)
    )
    memory = [d.memory_stats() for d in comm.devices]
    per_device = None
    if all(m is not None for m in memory):
        in_use = [m["bytes_in_use"] for m in memory]
        per_device = {
            "bytes_in_use": in_use,
            "peak_bytes_in_use": [m["peak_bytes_in_use"] for m in memory],
        }
        # params + momentum live on EVERY chip, and none holds the lot
        assert min(in_use) >= 2 * param_bytes, (in_use, param_bytes)
        assert max(in_use) <= 2 * min(in_use), in_use

    return {
        "status": "ok",
        "model": "resnet50" if not rehearse else "resnet-1-1-1-1 (rehearsal)",
        "image": image, "classes": classes, "dtype": jnp.dtype(dtype).name,
        "per_chip_batch": per_chip, "global_batch": global_batch,
        "steps": len(losses),
        "loss_first_epoch": round(float(first), 4),
        "loss_last_epoch": round(float(last), 4),
        "loss_first_step": round(losses[0], 4),
        "loss_last_step": round(losses[-1], 4),
        "replica_divergence": divergence,
        "compile_s": round(steps[0][1], 3),
        "step_s": round(float(np.median([s[1] for s in steps[1:]])), 4),
        "backend_compile_s": round(ledger.compile_s - compile_s_before, 3),
        "compilations_first_step": steps[0][2] - compiles_before,
        "compilations_after_first_step": late_compiles,
        "persistent_cache_hits": ledger.cache_hits - hits_before,
        "input_stall_s": round(float(state["input_stall"]), 3),
        "param_bytes": int(param_bytes),
        "per_device_memory": per_device,
    }


# ---------------------------------------------------------------------------
# phase 3: eager collectives on the default route, against numpy
# ---------------------------------------------------------------------------


def phase_collectives(mpi, rehearse: bool) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from torchmpi_tpu import constants

    comm = mpi.current_communicator()
    p = comm.size
    platform = comm.devices[0].platform
    cutoff = constants.get(
        f"small_allreduce_size_{constants.platform_suffix(platform)}"
    )
    rng = np.random.RandomState(0)
    checked = []

    def close(name, got, want):
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=1e-5, atol=1e-5, err_msg=name
        )
        checked.append(name)

    # one size below and one above the cutoff: the upper one leaves the
    # fused XLA op for the selector's bandwidth backend (on TPU the
    # hand-written ppermute ring)
    for n in (cutoff // 16, cutoff * 4):
        x = rng.randn(p, n).astype(np.float32)
        close(f"allreduce[{n}]", mpi.allreduce_tensor(jnp.asarray(x)),
              np.tile(x.sum(0), (p, 1)))
    n = 1 << 12 if rehearse else 1 << 18
    x = rng.randn(p, n).astype(np.float32)
    close("broadcast", mpi.broadcast_tensor(jnp.asarray(x), root=p - 1),
          np.tile(x[p - 1], (p, 1)))
    close("allgather", mpi.allgather_tensor(jnp.asarray(x)),
          np.tile(x.reshape(-1), (p, 1)))
    close("reducescatter", mpi.reducescatter_tensor(jnp.asarray(x)),
          x.sum(0).reshape(p, n // p))
    a2a = rng.randn(p, p, n // p).astype(np.float32)
    close("alltoall", mpi.alltoall_tensor(jnp.asarray(a2a)),
          a2a.transpose(1, 0, 2))
    handle = mpi.async_.allreduce_tensor(jnp.asarray(x))
    close("async allreduce + wait", mpi.wait(handle),
          np.tile(x.sum(0), (p, 1)))
    return {
        "status": "ok",
        "checked": checked,
        # the selector picks xla or a custom ring; which custom ring runs
        # above the cutoff is the ring_implementation constant
        "selector": {
            f"{op}/{mode}": backend
            for (op, mode), backend in sorted(comm._selector_cache.items())
        },
        "ring_implementation": constants.get("ring_implementation"),
    }


# ---------------------------------------------------------------------------
# phase 5: every Pallas entry point through the compiler
# ---------------------------------------------------------------------------


def _kernel(results: dict, verdict: dict, name: str, run, check) -> None:
    """Run one kernel's public route. Mosaic refusing to compile it is
    recorded; anything else — a wrong result included — propagates."""
    try:
        got = run()
    except Exception as e:  # noqa: BLE001 - re-raised unless a Mosaic refusal
        refusal = _mosaic_refusal(e)
        if refusal is None:
            raise
        results[name] = {"compiled": False, "error": refusal}
        return
    check(got)
    results[name] = {"matched": True, **verdict}


def phase_kernels(mpi, rehearse: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchmpi_tpu import constants
    from torchmpi_tpu.ops import accumulate, ring_kernels, scale_accumulate
    from torchmpi_tpu.parallel import ring_self_attention

    comm = mpi.current_communicator()
    p = comm.size
    rng = np.random.RandomState(1)
    results: dict = {}
    # what a pass means here: through Mosaic on a chip, the Pallas
    # interpreter in rehearsal
    verdict = {"interpreted": True} if rehearse else {"compiled": True}
    kernel = functools.partial(_kernel, results, verdict)

    def close(want, rtol=1e-5, atol=1e-5):
        return lambda got: np.testing.assert_allclose(
            np.asarray(got, np.float32), want, rtol=rtol, atol=atol
        )

    n = 1 << 14 if rehearse else 1 << 22
    a, b = rng.randn(n).astype(np.float32), rng.randn(n).astype(np.float32)
    kernel("accumulate",
           lambda: accumulate(jnp.asarray(a), jnp.asarray(b),
                              interpret=rehearse), close(a + b))
    kernel("scale_accumulate",
           lambda: scale_accumulate(jnp.asarray(a), jnp.asarray(b), -0.25,
                                    interpret=rehearse), close(a - 0.25 * b))
    if p < 2:
        return {"status": "ok", "kernels": results,
                "skipped": "ring kernels and ring attention: one chip"}

    # ---- ring collectives via mpi.pallas.*, the small-message reroutes
    # (to XLA, to the tree broadcast) switched off so the route reaches
    # the kernel at either size; the kernels' own step ledger proves it did
    n = 1 << 13 if rehearse else 1 << 20
    x = rng.randn(p, n).astype(np.float32)
    xj = jnp.asarray(x)
    total = np.tile(x.sum(0), (p, 1))
    ledger = ring_kernels._LAST_STEP_COUNTS

    def routed(key, fn):
        def run():
            ledger.clear()
            out = jax.block_until_ready(fn())
            assert key in ledger, f"route did not reach the {key} kernel"
            return out
        return run

    suffix = constants.platform_suffix(comm.devices[0].platform)
    cutoffs = tuple(
        f"{name}_{suffix}" for name in (
            "small_allreduce_size", "small_broadcast_size",
            "broadcast_size_tree_based",
        )
    )
    previous = {
        k: constants.get(k)
        for k in ("ring_implementation", "wire_quant_min_elements") + cutoffs
    }
    ring_kernels._FORCE_INTERPRET = rehearse
    try:
        constants.set("wire_quant_min_elements", 1)
        for k in cutoffs:
            constants.set(k, 0)
        constants.set("ring_implementation", "pallas")
        kernel("ring_allreduce_pallas",
               routed("allreduce", lambda: mpi.pallas.allreduce_tensor(xj)),
               close(total))
        kernel("ring_allreduce_pallas[int8 wire]",
               routed("allreduce", lambda: mpi.pallas.allreduce_tensor(
                   xj, wire_dtype="int8")),
               # quantization error is bounded relative to the payload scale
               close(total, rtol=0, atol=2e-2 * float(np.abs(total).max())))
        kernel("ring_reduce_scatter_pallas",
               routed("reduce_scatter",
                      lambda: mpi.pallas.reducescatter_tensor(xj)),
               close(x.sum(0).reshape(p, n // p)))
        kernel("ring_allgather_pallas",
               routed("allgather", lambda: mpi.pallas.allgather_tensor(xj)),
               close(np.tile(x.reshape(-1), (p, 1)), rtol=0, atol=0))
        kernel("ring_broadcast_pallas",
               routed("broadcast",
                      lambda: mpi.pallas.broadcast_tensor(xj, root=p - 1)),
               close(np.tile(x[p - 1], (p, 1)), rtol=0, atol=0))
        reduced = x.copy()
        reduced[1] = x.sum(0)
        kernel("ring_reduce_pallas",
               routed("reduce", lambda: mpi.pallas.reduce_tensor(xj, root=1)),
               close(reduced))
        constants.set("ring_implementation", "pallas_bidir")
        # two chips share one link per pair: bidir delegates to the
        # unidirectional kernel by design
        kernel("ring_allreduce_bidir_pallas",
               routed("allreduce_bidir" if p > 2 else "allreduce",
                      lambda: mpi.pallas.allreduce_tensor(xj)),
               close(total))
    finally:
        ring_kernels._FORCE_INTERPRET = False
        for k, v in previous.items():
            constants.set(k, v)

    # ---- ring attention on an sp axis over every chip, vs the XLA ring
    batch, heads, d = (1, 2, 8) if rehearse else (2, 8, 64)
    seq = 16 * p if rehearse else 4096
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    tol = dict(rtol=1e-4, atol=1e-4) if rehearse else dict(rtol=5e-2, atol=5e-2)
    mesh = Mesh(np.array(comm.devices), ("sp",))
    spec = P(None, "sp")
    shard = NamedSharding(mesh, spec)
    q, k, v, w = (
        jax.device_put(
            jnp.asarray(rng.randn(batch, seq, heads, d), dtype), shard
        )
        for _ in range(4)
    )
    kind = "pallas_interpret" if rehearse else "pallas"

    def attention(backend):
        return jax.jit(jax.shard_map(
            lambda q, k, v: ring_self_attention(
                q, k, v, axis="sp", causal=True, backend=backend
            ),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
        ))

    def grads(backend):
        def loss(q, k, v, w):
            out = ring_self_attention(
                q, k, v, axis="sp", causal=True, backend=backend
            )
            return jax.lax.psum(
                jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), "sp"
            )
        return jax.jit(jax.shard_map(
            jax.grad(loss, argnums=(0, 1, 2)),
            mesh=mesh, in_specs=(spec,) * 4, out_specs=(spec,) * 3,
            check_vma=False,
        ))

    want = np.asarray(attention("xla")(q, k, v), np.float32)
    want_g = [np.asarray(g, np.float32) for g in grads("xla")(q, k, v, w)]
    gscale = max(float(np.abs(g).max()) for g in want_g)
    kernel("ring_attention forward",
           lambda: attention(kind)(q, k, v), close(want, **tol))
    kernel("ring_attention forward bidir",
           lambda: attention(kind + "_bidir")(q, k, v), close(want, **tol))

    def check_grads(got):
        for g, ref in zip(got, want_g):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), ref,
                rtol=tol["rtol"], atol=tol["atol"] * gscale,
            )

    kernel("ring_attention backward (_full)",
           lambda: grads(kind + "_full")(q, k, v, w), check_grads)
    return {"status": "ok", "kernels": results}


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse", action="store_true",
        help="run without a chip: tiny shapes on a 4-device CPU mesh, "
        "interpret-mode kernels; the output is marked \"rehearsal\": true",
    )
    args = ap.parse_args(argv)
    if args.rehearse:
        # exactly four CPU devices, whatever the caller's XLA_FLAGS held
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "",
            os.environ.get("XLA_FLAGS", ""),
        )
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4"
        ).strip()
    # a hung kernel or collective must end as a failure with a traceback
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)

    import jax

    import torchmpi_tpu as mpi
    from torchmpi_tpu.utils.compile_cache import use_compile_cache

    # the rehearsal keeps no cache: nothing in it is worth a second run,
    # and XLA:CPU warns on every reload
    cache_dir = None if args.rehearse else use_compile_cache(HERE)
    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and platform != "tpu":
        print(
            f"chip_smoke.py: no TPU (jax.devices()[0].platform == "
            f"{platform!r}, JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}); nothing was run. "
            "--rehearse runs the CPU rehearsal.",
            file=sys.stderr,
        )
        return 2
    ledger = CompileLedger()
    t_start = time.perf_counter()
    mpi.start(with_tpu=not args.rehearse)
    device = {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    versions = {
        name: importlib.metadata.version(name)
        for name in ("jax", "jaxlib", "libtpu")
    }
    print(f"# chip_smoke: device {device} versions {versions} "
          f"compile cache {cache_dir}", flush=True)
    phases: dict = {}

    def run(name, fn, *fn_args, needs: int = 1):
        if len(devices) < needs:
            phases[name] = {
                "status": "skipped",
                "reason": f"needs >= {needs} chips, found {len(devices)}",
            }
        else:
            t0 = time.perf_counter()
            phases[name] = fn(*fn_args)
            phases[name]["seconds"] = round(time.perf_counter() - t0, 1)
        print(f"# chip_smoke: {name}: {json.dumps(phases[name])}", flush=True)

    run("resnet50_train", phase_resnet, mpi, args.rehearse, ledger)
    run("collectives", phase_collectives, mpi, args.rehearse, needs=2)

    def phase_layouts():
        import __graft_entry__

        # dryrun_multichip owns its own start()/stop()
        mpi.stop()
        try:
            ran_on = __graft_entry__.dryrun_multichip(4)
        finally:
            mpi.start(with_tpu=not args.rehearse)
        assert all(d.platform == platform for d in ran_on), ran_on
        return {"status": "ok", "devices": [str(d) for d in ran_on]}

    run("parallel_layouts", phase_layouts, needs=4)
    run("kernels", phase_kernels, mpi, args.rehearse)
    mpi.stop()

    summary = {
        "ok": True,
        "device": device,
        "rehearsal": args.rehearse,
        "versions": versions,
        "compile_cache_dir": cache_dir,
        "seconds": round(time.perf_counter() - t_start, 1),
        "phases": phases,
        "claim": None,
    }
    print(json.dumps(summary), flush=True)
    # the driver's contract for the LAST line: these keys and no others
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
