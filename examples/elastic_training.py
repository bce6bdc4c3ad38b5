"""Elastic training: crash-mid-job, relaunch, resume from checkpoint.

The reference had no recovery — a dead rank meant manual ``pkill`` and a
cold restart (``dependencies/README.md:46-49``). Here the launcher's
``--max-restarts`` relaunches the whole world when a rank dies, and this
script shows the contract a trainer implements to survive that:

1. checkpoint every epoch (``utils.checkpoint.save_engine``);
2. on startup, restore if a checkpoint exists and continue from its
   epoch (``TORCHMPI_TPU_RESTART_COUNT`` says which attempt this is);
3. the final loss matches an uninterrupted run: the restart is exact
   because ``train_resident`` epochs are seeded per epoch index.

Run (2 controller processes; rank 1 crashes mid-training on the first
attempt, the relaunch resumes and finishes):

    python -m torchmpi_tpu.launch --nproc 2 --cpu-devices 2 \
        --max-restarts 1 examples/elastic_training.py -- \
        --crash-at-epoch 2 --ckpt /tmp/elastic_ck

Single-process demo (no launcher, no crash):

    python examples/elastic_training.py --cpu-mesh 8 --ckpt /tmp/ck
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--ckpt", required=True, help="checkpoint directory")
    ap.add_argument(
        "--crash-at-epoch", type=int, default=0,
        help="rank 1 aborts after checkpointing this epoch, on the FIRST "
        "launcher attempt only (0 = never crash)",
    )
    ap.add_argument("--cpu-mesh", type=int, default=0)
    args = ap.parse_args()

    if args.cpu_mesh:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.cpu_mesh}"
        ).strip()
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    import numpy as np
    import optax

    import torchmpi_tpu as mpi
    from torchmpi_tpu.engine import AllReduceSGDEngine
    from torchmpi_tpu.models import MLP6, init_params, make_loss_fn
    from torchmpi_tpu.utils import checkpoint, synthetic_mnist

    mpi.start()
    restart = int(os.environ.get("TORCHMPI_TPU_RESTART_COUNT", "0"))

    (xtr, ytr), _ = synthetic_mnist(num_train=2048, num_test=1)
    model = MLP6(features=64)
    params = init_params(model, (1, 28, 28))
    engine = AllReduceSGDEngine(
        make_loss_fn(model), params, optimizer=optax.sgd(0.05)
    )

    start_epoch = 0
    ckdir = Path(args.ckpt)
    if ckdir.exists() and any(ckdir.iterdir()):
        # no fallback: in a multi-process job a one-sided restore failure
        # would leave ranks on DIFFERENT epochs and hang the next
        # collective — fail the attempt loudly and let --max-restarts
        # retry the whole world instead
        meta = checkpoint.restore_engine(ckdir, engine)
        start_epoch = int(meta.get("step", 0))
        print(
            f"[attempt {restart}] resumed from checkpoint at epoch "
            f"{start_epoch}",
            flush=True,
        )

    losses = []
    for epoch in range(start_epoch, args.epochs):
        state = engine.train_resident(
            xtr, ytr, args.batch, max_epochs=1, seed=100 + epoch,
            shuffle=False,
        )
        loss = float(np.asarray(state["losses"])[-1])
        losses.append(loss)
        checkpoint.save_engine(ckdir, engine, step=epoch + 1)
        mpi.barrier()
        print(f"[attempt {restart}] epoch {epoch}: loss={loss:.4f}", flush=True)
        if (
            args.crash_at_epoch
            and restart == 0
            and epoch + 1 == args.crash_at_epoch
            and mpi.rank() != 0
            and mpi.num_processes() > 1
        ):
            print("[attempt 0] injected crash", flush=True)
            os.abort()

    if losses:
        print(f"final: epoch={args.epochs} loss={losses[-1]:.4f}", flush=True)
    else:  # resumed past the last epoch: nothing left to train
        print(f"final: epoch={args.epochs} already complete", flush=True)
    mpi.barrier()
    mpi.stop()


if __name__ == "__main__":
    main()
