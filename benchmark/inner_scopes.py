"""Device time by the scopes a model opens INSIDE its forward pass:
``tm.attn.full``, ``tm.attn.window``, ``tm.moe.route``, ``tm.moe.experts``,
``tm.moe.combine`` (``torchmpi_tpu/telemetry/spans.py``
``MODEL_SCOPE_NAMES``).

``scopes.scope_of`` gives an operation to the FIRST ``tm.`` component of
its ``op_name``, which for all of these is ``tm.fwd_bwd``: that reading
stays whole. Here an operation goes to the LAST ``tm.attn.*`` / ``tm.moe.*``
name anywhere in its ``op_name``, forward and backward alike: backward's
operations carry the forward's path again behind jax's wrappers
(``tm.fwd_bwd/transpose(jvp(MoEDecoder))/tm.fwd_bwd/jvp(MoEDecoder)/
checkpoint/rematted_computation/MoEDecoderBlock_1/tm.attn.window/while/
body/dot_general``), and a wrapper may also hold a whole path in its
brackets, so the name is looked for in the string, not among the
components between slashes. The trace's reading (``scopes.op_names``) and
the interval arithmetic (``scopes.own_intervals``, ``xplane``) are the
other readers'.

**The grouped products.** XLA makes ``lax.ragged_dot`` its own kernel on
the TPU and gives that kernel's events no ``op_name`` but its own
(``ragged-dot-none:``, and ``ragged-dot-metadata:`` for the tile table
before it), so no scope reaches them. The program's only grouped products
are the expert layer's, so those events are read as ``tm.moe.experts``
(whether one is forward or backward cannot be told). ``scopes.by_scope``
counts them under no scope: ``unscoped_device_share`` shows them.

A program without these scopes (any other model, or the parent of the PR
that added them) gives None, and the reader leaves its metric out.
"""

from __future__ import annotations

import functools
import re
from collections import defaultdict

from benchmark import scopes, xplane

INNER = re.compile(r"tm\.(?:attn|moe)\.[A-Za-z0-9_]+")
GROUPED_PRODUCT = "ragged-dot"  # XLA's kernel for lax.ragged_dot


def inner_scope_of(op_name: str):
    """The innermost ``tm.attn.*`` / ``tm.moe.*`` name of an ``op_name``,
    seen through ``transpose(jvp(...))`` and ``checkpoint`` wrappers; XLA's
    grouped-product kernel is the expert layer's; None otherwise."""
    found = INNER.findall(op_name)
    if found:
        return found[-1]
    return "tm.moe.experts" if op_name.startswith(GROUPED_PRODUCT) else None


@functools.lru_cache(maxsize=4)
def by_inner_scope(path: str):
    """One trace, averaged over its chips: {"scope_s": seconds under each
    inner scope (the union of its operations' own intervals), "backward_s":
    the part of that whose ``op_name`` passes through a ``transpose(``
    wrapper, "steps"}. None where the trace holds no device operation or
    none of them lies under such a scope."""
    file = path if str(path).endswith(".pb") else xplane.find(path)
    trace = xplane.load(file)
    if not trace.ops:
        return None
    names = scopes.op_names(file)
    seconds, backward = defaultdict(float), defaultdict(float)
    n = len(trace.ops)
    for plane, events in trace.ops.items():
        table = names.get(plane, {})
        spans, back = defaultdict(list), defaultdict(list)
        for name, pieces in scopes.own_intervals(events):
            op = table.get(name, "")
            scope = inner_scope_of(op)
            if scope is None:
                continue
            spans[scope] += pieces
            if "transpose(" in op:
                back[scope] += pieces
        for scope, intervals in spans.items():
            seconds[scope] += xplane.length(xplane.union(intervals)) / n
            backward[scope] += xplane.length(
                xplane.union(back[scope])) / n
    if not seconds:
        return None
    first = sorted(trace.ops)[0]
    return {
        "scope_s": dict(seconds),
        "backward_s": dict(backward),
        "steps": xplane.step_count(trace.modules.get(first, [])),
    }


def inner_ms_per_step(run, *names: str):
    """Milliseconds a step of the steady trace spends under the inner
    scopes ``names``; None where the program has no such scope."""
    path, _ = run["phase"]["traces"]["steady"]
    found = by_inner_scope(str(path))
    if found is None:
        return None
    steps = run["phase"].get("traced_steps") or found["steps"]
    if not steps:
        return None
    return 1e3 * sum(found["scope_s"].get(n, 0.0) for n in names) / steps
