"""Long-context LM training with ring-attention sequence parallelism.

The sequence axis is sharded over the ``sp`` mesh axis: no device ever holds
the full context, k/v blocks rotate around the ring (one ICI hop per step),
and the streaming-softmax keeps attention exact. Composable with data
parallelism: mesh (dp x sp), gradients psum over both axes.

Task: next-token prediction on a periodic token stream (period 17 forces the
model to attend across positions).

Run: python examples/long_context.py [--cpu-mesh 8] [--seq 512] [--sp 4]
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--sp", type=int, default=4)
    ap.add_argument("--cpu-mesh", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--sp-backend",
        default="xla",
        choices=["xla", "pallas", "pallas_interpret"],
        help="ring-attention transport: XLA ppermute ring, the Pallas "
        "RDMA kernel (real multi-chip TPU) or its interpret mode (CPU "
        "mesh)",
    )
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
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import torchmpi_tpu as mpi
    from torchmpi_tpu.data import InputPipeline
    from torchmpi_tpu.models import LongContextTransformer
    from torchmpi_tpu.parallel import make_parallel_mesh
    from torchmpi_tpu.utils.flops import (
        mfu,
        train_flops,
        transformer_forward_flops,
    )

    mpi.start()
    comm = mpi.current_communicator()
    p = comm.size
    sp = args.sp if p % args.sp == 0 else 1
    dp = p // sp
    mesh = make_parallel_mesh(comm, axes={"dp": dp, "sp": sp})
    print(f"ranks={p} mesh=dp{dp} x sp{sp} seq={args.seq}")

    model = LongContextTransformer(
        sp_axis="sp" if sp > 1 else None,
        sp_backend=args.sp_backend,
        max_len=args.seq,
        num_layers=2,
    )
    opt = optax.adam(args.lr)

    rng = np.random.RandomState(args.seed)

    def make_batch(n):
        # periodic stream: token[t] = (phase + t) % 17, mapped into vocab
        phase = rng.randint(0, 17, (n, 1))
        t = np.arange(args.seq)[None, :]
        return ((phase + t) % 17 + 5).astype(np.int32)

    def init_fn(tokens):
        return model.init(jax.random.PRNGKey(args.seed), tokens)["params"]

    # init on the sp-sharded sequence (param shapes are seq-independent)
    tokens0 = jnp.asarray(make_batch(dp * args.batch))
    params = jax.jit(
        jax.shard_map(
            init_fn,
            mesh=mesh,
            in_specs=P("dp", "sp"),
            out_specs=P(),
            check_vma=False,
        )
    )(tokens0)

    opt_state = opt.init(params)

    def step(params, opt_state, tokens):
        # tokens: local [B_dp, T_sp]; inputs/targets shifted globally:
        # predict token[t+1] from token[<=t]; the last local target comes
        # from the neighbor's first token via a ring shift
        inputs = tokens
        from torchmpi_tpu.collectives.primitives import shift

        nxt = shift(tokens[:, :1], offset=-1, axis="sp")  # neighbor's first
        targets = jnp.concatenate([tokens[:, 1:], nxt], axis=1)

        def loss_fn(params):
            logits = model.apply({"params": params}, inputs)
            logp = jax.nn.log_softmax(logits)
            ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
            # mask the final global position (no target exists for it)
            sp_rank = jax.lax.axis_index("sp")
            t_local = tokens.shape[1]
            is_last = (sp_rank == sp - 1) & (
                jnp.arange(t_local) == t_local - 1
            )
            ll = jnp.where(is_last[None, :], 0.0, ll)
            return -jnp.sum(ll) / (tokens.shape[0] * (t_local - 1))

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, ("dp", "sp")), grads
        )
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, jax.lax.pmean(loss, ("dp", "sp"))

    step_fn = jax.jit(
        jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(P(), P(), P("dp", "sp")),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )

    # token feed through the streaming input pipeline: the whole run's
    # batches pre-generated with the SAME rng draw order the inline loop
    # used, then served in order (shuffle=False) by background producers
    # with device-side prefetch onto the (dp x sp) sharding — the step
    # only ever waits on input when the producers fall behind, and that
    # wait is measured separately from compute
    import time

    from jax.sharding import NamedSharding

    all_tokens = np.concatenate(
        [make_batch(dp * args.batch) for _ in range(args.steps)]
    )
    pipe = InputPipeline(
        (all_tokens, np.zeros(len(all_tokens), np.int32)),
        batch_size=dp * args.batch,
        num_ranks=1,
        shuffle=False,
        # drop the pipeline's rank-stacking axis (single-host feed) so
        # tokens prefetch straight onto the step's (dp x sp) layout;
        # the dummy labels are unused — replicated
        transform=lambda xb, yb: (xb.reshape(-1, args.seq), yb.reshape(-1)),
        sharding=(
            NamedSharding(mesh, P("dp", "sp")),
            NamedSharding(mesh, P()),
        ),
    )

    loss = None
    input_stall_s = 0.0
    t_start = time.perf_counter()
    batches = iter(pipe)
    for s in range(args.steps):
        t_fetch = time.perf_counter()
        tokens, _ = next(batches)
        input_stall_s += time.perf_counter() - t_fetch
        params, opt_state, loss = step_fn(params, opt_state, tokens)
        if s % 10 == 0 or s == args.steps - 1:
            print(f"step {s}: loss={float(np.asarray(loss)):.4f}")
    jax.block_until_ready(loss)
    elapsed = time.perf_counter() - t_start

    # first-ever throughput/MFU numbers for the long-context line:
    # per-token training FLOPs from the analytic model walk, achieved
    # rate from the run itself, input stall reported alongside so a
    # starved pipeline can't masquerade as a slow model
    flops_per_token = train_flops(
        transformer_forward_flops(
            args.seq, model.d_model, model.num_layers, model.num_heads,
            model.head_dim, model.vocab_size,
        )
    ) // args.seq
    tokens_per_sec = args.steps * dp * args.batch * args.seq / max(
        elapsed, 1e-9
    )
    achieved, frac = mfu(tokens_per_sec / p, flops_per_token, jax.devices()[0])
    print(
        f"throughput: {tokens_per_sec:,.0f} tok/s "
        f"({tokens_per_sec / p:,.0f}/chip), "
        f"{achieved / 1e12:.3f} TFLOP/s/chip"
        + (f", MFU {frac:.1%}" if frac is not None
           else " (no TPU peak table entry: MFU n/a)")
        + f", input stall {input_stall_s:.3f}s of {elapsed:.1f}s"
    )

    final = float(np.asarray(loss))
    print(f"final: loss={final:.4f} (random = {np.log(17):.4f})")
    mpi.stop()
    return final


if __name__ == "__main__":
    main()
