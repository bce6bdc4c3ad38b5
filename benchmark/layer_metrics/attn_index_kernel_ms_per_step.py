"""Attention over selected keys, the indexer's score kernel itself
(parallel/selected_attention.py ``_index_scores``: float32 products at
precision highest over every causal pair, a panel of 4,096 queries a call):
the device time of the kernel's own events per optimizer step of the steady
trace. It says how many times a step the scores are made: 15.2 ms over a
layer's four panels on a v5e (measured, PR 36), once in forward and, before
the PR that kept the panels for backward, once more there.

Found by the events' names as ``attn_kernel_ms_per_step`` finds its kernels
(an event's name is its HLO instruction, ``%tm_attn_index_scores.7 = ...``),
not by the ``tm.attn.index`` scope, which also holds the indexer's
projections. The name is held here and pinned to the ``name=`` of the
program's ``pallas_call`` by a test, so that the parent of the PR that added
this file reads too. A step in which no such kernel ran (another model)
gives None and the line leaves the metric out."""

KERNEL = "tm_attn_index_scores"


def read(run):
    steady = run["steady"]
    steps = run["phase"].get("traced_steps") or steady.get("steps")
    # an event's name is its HLO instruction: "%<name>.<n> = ..."
    times = [t for name, t in steady.get("op_times", {}).items()
             if name.lstrip("%").startswith(KERNEL)]
    if not steps or not times:
        return None
    return 1e3 * sum(times) / steps
