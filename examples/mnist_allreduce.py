"""MNIST synchronous AllReduce-SGD — parity with
``examples/mnist/mnist_allreduce.lua``: logistic regression, lr 0.2, global
batch 336 split over ranks, 5 epochs; distributed loss must match the
sequential baseline and replicas must stay consistent.

Run:  python examples/mnist_allreduce.py [--model lenet] [--epochs 5]
      [--cpu-mesh N]
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="logreg", choices=["logreg", "lenet"])
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--lr", type=float, default=0.2)
    ap.add_argument("--batch", type=int, default=336)
    ap.add_argument(
        "--cpu-mesh",
        type=int,
        default=0,
        help="force an N-device virtual CPU mesh (0 = use real devices)",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.cpu_mesh:
        import os

        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.cpu_mesh}"
        )
        import jax

        jax.config.update("jax_platforms", "cpu")

    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import torchmpi_tpu as mpi
    from torchmpi_tpu import nn as mpinn
    from torchmpi_tpu.engine import AllReduceSGDEngine
    from torchmpi_tpu.models import (
        LeNet,
        LogisticRegression,
        accuracy,
        init_params,
        make_loss_fn,
    )
    from torchmpi_tpu.utils import DistributedIterator, synthetic_mnist

    mpi.start()
    comm = mpi.current_communicator()
    p = comm.size
    print(f"ranks={p} nodes={comm.num_nodes()}")

    (xtr, ytr), (xte, yte) = synthetic_mnist(seed=args.seed)
    batch = max(1, args.batch // p) * p  # divisible global batch (336/size model)

    model = LeNet() if args.model == "lenet" else LogisticRegression()
    params = init_params(model, (1, 28, 28), seed=args.seed)
    loss_fn = make_loss_fn(model)

    engine = AllReduceSGDEngine(
        loss_fn,
        params,
        optimizer=optax.sgd(args.lr),
        comm=comm,
        hooks={
            "on_end_epoch": lambda s: print(
                f"epoch {s['epoch']}: loss={s['losses'][-1]:.4f}"
            )
        },
    )
    it = DistributedIterator(
        xtr, ytr, batch, p, seed=args.seed, sharding=engine.batch_sharding
    )
    state = engine.train(lambda: iter(it), max_epochs=args.epochs)

    # replica consistency (checkWithAllreduce invariant, init.lua:372-395)
    stacked = jax.tree_util.tree_map(
        lambda w: np.broadcast_to(np.asarray(w), (p,) + np.asarray(w).shape),
        jax.device_get(engine.params),
    )
    mpinn.check_with_allreduce(stacked, comm)

    # test accuracy
    final = jax.device_get(engine.params)
    logits = model.apply({"params": final}, xte)
    acc = float(accuracy(logits, yte))
    sps = state["samples"] / state["time"]
    print(
        f"final: loss={state['losses'][-1]:.4f} test_acc={acc:.4f} "
        f"samples/sec={sps:.0f} samples/sec/chip={sps / p:.0f}"
    )
    mpi.stop()
    return state["losses"][-1], acc


if __name__ == "__main__":
    main()
