"""ResNet ImageNet-shaped data-parallel AllReduce-SGD — BASELINE.json
config #4 ("ResNet-50 ImageNet data-parallel via synchronizeGradients"):
cross-replica gradient sum + batch-norm statistics sync every step through
the engine, driven by the synthetic ImageNet input pipeline
(zero-egress environment; ``--data-dir`` hooks real IDX-style data in).

The reference drove big models through the same two calls this engine
compiles in-graph: ``mpinn.synchronizeGradients`` per step and a one-shot
``synchronizeParameters`` (``torchmpi/nn.lua:32-56``).

Run:  python examples/resnet_allreduce.py --cpu-mesh 8 --model resnet18 \
          --image-size 32 --train 256 --epochs 2
      python examples/resnet_allreduce.py          # TPU: ResNet-50, 224px
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50", choices=["resnet18", "resnet50"])
    ap.add_argument("--classes", type=int, default=1000)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--train", type=int, default=1024)
    ap.add_argument("--test", type=int, default=128)
    ap.add_argument("--per-rank-batch", type=int, default=32)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--bf16", action="store_true", help="bfloat16 compute")
    ap.add_argument(
        "--fsdp",
        action="store_true",
        help="ZeRO-3: shard params + optimizer state over the data axis",
    )
    ap.add_argument(
        "--accum-steps",
        type=int,
        default=1,
        help="gradient accumulation microbatches per step",
    )
    ap.add_argument(
        "--cpu-mesh",
        type=int,
        default=0,
        help="force an N-device virtual CPU mesh (0 = use real devices)",
    )
    ap.add_argument(
        "--streaming",
        action="store_true",
        help="feed epochs through the torchmpi_tpu.data streaming input "
        "pipeline (background producers + device prefetch) instead of "
        "device-resident epochs",
    )
    ap.add_argument(
        "--input-workers",
        type=int,
        default=0,
        help="producer threads for --streaming (0 = input_workers knob)",
    )
    args = ap.parse_args(argv)

    if args.cpu_mesh:
        import os

        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.cpu_mesh}"
        )
        import jax

        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np
    import optax

    import torchmpi_tpu as mpi
    from torchmpi_tpu.engine import AllReduceSGDEngine
    from torchmpi_tpu.models import (
        ResNet18,
        ResNet50,
        accuracy,
        init_resnet,
        make_stateful_loss_fn,
    )
    from torchmpi_tpu.utils import synthetic_imagenet
    from torchmpi_tpu.utils.flops import (
        mfu,
        resnet_forward_flops,
        train_flops,
    )

    mpi.start()
    p = mpi.size()
    print(f"[resnet] world size {p}: {mpi.current_communicator().describe()}")

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    ctor = ResNet50 if args.model == "resnet50" else ResNet18
    model = ctor(num_classes=args.classes, dtype=dtype)
    params, batch_stats = init_resnet(model, args.image_size)

    (xtr, ytr), (xte, yte) = synthetic_imagenet(
        num_train=args.train,
        num_test=args.test,
        num_classes=args.classes,
        image_size=args.image_size,
    )

    if args.model == "resnet50":
        fwd_flops = resnet_forward_flops(
            args.image_size, num_classes=args.classes
        )
    else:
        fwd_flops = resnet_forward_flops(
            args.image_size, stage_sizes=(2, 2, 2, 2), bottleneck=False,
            num_classes=args.classes,
        )
    flops_per_sample = train_flops(fwd_flops)

    engine = AllReduceSGDEngine(
        make_stateful_loss_fn(model),
        params,
        optimizer=optax.sgd(args.lr, momentum=args.momentum),
        model_state=batch_stats,
        param_sharding="fsdp" if args.fsdp else "replicated",
        accum_steps=args.accum_steps,
        flops_per_sample=flops_per_sample,
    )

    def log_epoch(epoch, loss, secs):
        ips = args.per_rank_batch * p * (
            (args.train // p // args.per_rank_batch) or 1
        ) / max(secs, 1e-9)
        print(
            f"[resnet] epoch {epoch}: loss {loss:.4f}  "
            f"{secs:.2f}s  {ips:,.0f} img/s ({ips / p:,.0f}/chip)"
        )

    if args.streaming:
        from torchmpi_tpu.data import InputPipeline

        pipe = InputPipeline(
            (xtr, ytr),
            batch_size=args.per_rank_batch * p,
            num_ranks=p,
            sharding=engine.batch_sharding,
            workers=args.input_workers or None,
            # same host-side cast the resident path's image_dtype does,
            # but on the producer threads (ml_dtypes gives numpy bf16)
            transform=(
                (lambda xb, yb: (xb.astype(jnp.bfloat16), yb))
                if args.bf16 else None
            ),
        )
        state = engine.train(pipe, max_epochs=args.epochs)
        print(
            f"[resnet] streaming input: {len(pipe)} batches/epoch, "
            f"input stall {state['input_stall']:.3f}s "
            f"(producer-side consumer stall {pipe.consumer_stall_s:.3f}s)"
        )
    else:
        state = engine.train_resident(
            xtr,
            ytr,
            args.per_rank_batch,
            max_epochs=args.epochs,
            image_dtype=dtype if args.bf16 else None,
            epoch_callback=log_epoch,
        )

    # throughput + model-FLOPs utilization, computed from the run itself
    # (fraction-of-peak is None off-TPU — printed as the raw FLOP/s then)
    import jax

    ips = state["samples"] / max(state["time"], 1e-9)
    achieved, frac_incl = mfu(ips / p, flops_per_sample, jax.devices()[0])
    busy = max(state["time"] - state.get("input_stall", 0.0), 1e-9)
    print(
        f"[resnet] throughput {ips:,.0f} img/s ({ips / p:,.0f}/chip), "
        f"{achieved / 1e12:.3f} TFLOP/s/chip"
        + (
            f", MFU {frac_incl * state['time'] / busy:.1%} "
            f"(incl. input stall {frac_incl:.1%})"
            if frac_incl is not None
            else " (no TPU peak table entry: MFU n/a)"
        )
    )

    def apply_fn(prm, st, x):
        return model.apply(
            {"params": prm, "batch_stats": st}, x, train=False
        )

    acc = engine.evaluate(apply_fn, xte, yte, accuracy)
    print(
        f"[resnet] {args.model} done: final loss {state['losses'][-1]:.4f}, "
        f"test acc {acc:.3f}, {state['samples']:,} samples in "
        f"{state['time']:.1f}s"
    )
    mpi.stop()
    return state, acc


if __name__ == "__main__":
    main()
