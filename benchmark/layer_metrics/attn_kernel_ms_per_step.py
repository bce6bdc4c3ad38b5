"""Attention, the fused kernels themselves (parallel/ring_attention.py
``_fused``: jax's splash-attention kernels, a forward that saves the
log-sum-exp and a backward that gives dQ, dK and dV): the device time of
the kernels' own events, forward, recomputation and backward, per
optimizer step of the steady trace.

Found by the events' names, not by a scope, so that no change of scopes or
of what the profiler writes about a custom call can hide the kernels or
lend them another operation's time: an event's name is its HLO instruction
(``%splash_mqa_fwd_residuals.3 = ...``), and the kernels' names are the
program's (``telemetry.names.ATTN_KERNEL_EVENT``, pinned to the kernels'
own naming by the repo's tests). On the chip the kernels' events also bear
the ``tm.attn.*`` scope they were traced under (PERF.md, PR 27), so this is
the part of ``attn_full_ms_per_step`` + ``attn_window_ms_per_step`` that
is the kernels'; the rest of those two is rotary position, the scale and
the layout changes around them. A program without the name (the parent of
the PR that added the kernels), or a step in which no such kernel ran
(narrower heads, another model), gives None and the line leaves the metric
out."""


def read(run):
    try:
        from torchmpi_tpu.telemetry import names
        kernel = names.ATTN_KERNEL_EVENT
    except (ImportError, AttributeError):
        return None
    steady = run["steady"]
    steps = run["phase"].get("traced_steps") or steady.get("steps")
    # an event's name is its HLO instruction: "%<name>.<n> = ..."
    times = [t for name, t in steady.get("op_times", {}).items()
             if name.lstrip("%").startswith(kernel)]
    if not steps or not times:
        return None
    return 1e3 * sum(times) / steps
