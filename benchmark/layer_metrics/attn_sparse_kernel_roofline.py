"""Attention over the selection, its kernels' share of their roofline
(parallel/selected_attention.py: jax's splash-attention kernels under the
selection's mask, forward and backward, and the kernel of the head-summed
probabilities): the least time the chip could take for what the algorithm
NEEDS (``benchmark/sparse_attention_roofline.py``: the products over the
selected pairs alone, the bf16 peak; or the bytes of q, k, v, the output and
their gradients, the HBM peak; whichever is larger) over the device time of
those kernels' events a step of the steady trace.

The kernels' events are found by their names (an event's name is its HLO
instruction, ``%splash_mqa_fwd_residuals.3 = ...``), which are the program's
(``parallel.selected_attention.SPARSE_KERNEL_EVENTS``). The program
multiplies every causal pair under a mask, twice forward (the block is
recomputed) and makes the probabilities again for the indexer's loss, so the
share reads low: that is the reading a later change moves. None where the
program has no such kernels (another model, the parent of the PR that added
them) or the configuration selects nothing."""


def read(run):
    try:
        from torchmpi_tpu.parallel.selected_attention import (
            SPARSE_KERNEL_EVENTS,
        )
    except ImportError:
        return None
    cfg = run["cfg"]
    if "sa_config" not in cfg:
        return None
    steady = run["steady"]
    steps = run["phase"].get("traced_steps") or steady.get("steps")
    times = [t for name, t in steady.get("op_times", {}).items()
             if name.lstrip("%").startswith(SPARSE_KERNEL_EVENTS)]
    if not steps or not times:
        return None
    import jax

    from benchmark import flops, sparse_attention_roofline as roofline

    kind = jax.devices()[0].device_kind
    least, _ = roofline.least_seconds(
        cfg, flops.peak_flops(kind), roofline.peak_hbm_bytes_per_s(kind))
    return 100.0 * least / (sum(times) / steps)
