"""Forward and backward divided among the scopes a language model opens
inside ``tm.fwd_bwd``, with nothing counted twice and nothing left out:
``tm.lm.embed``, ``tm.lm.norm``, ``tm.lm.mlp``, ``tm.lm.head``,
``tm.lm.loss``, ``tm.attn.*``, ``tm.moe.*``
(``torchmpi_tpu/telemetry/spans.py`` ``MODEL_SCOPE_NAMES``).

``inner_scopes.by_inner_scope`` sums, scope by scope, the union of the
scope's operations, and nothing checks that its parts make the whole. Here
every device operation of the steady trace gives its OWN intervals
(``scopes.own_intervals``: the time no operation nested in it covers) to
exactly one **bucket**:

- the innermost ``tm.lm.*`` / ``tm.attn.*`` / ``tm.moe.*`` name anywhere in
  its ``op_name`` (the LAST one: backward's operations carry the forward's
  path again behind jax's wrappers, and a wrapper may hold a path in its
  brackets, ``transpose(jvp(tm.lm.loss))/jit(log_softmax)``; where XLA
  merges two operations into one, their ``op_name``s come joined by ``;``
  and the last name of the whole string takes it, as in
  ``inner_scopes.py``);
- XLA's ``ragged-dot`` kernels, which bear no ``op_name`` but their own, to
  ``tm.moe.experts``, as ``inner_scopes.py`` reads them;
- else ``unnamed``, where the first ``tm.`` component of its ``op_name`` is
  ``tm.fwd_bwd``: what the model's scopes leave of forward and backward;
- an operation outside ``tm.fwd_bwd`` (the optimizer, the gradient sync, an
  operation of no scope) is not this reader's business.

and, across the buckets, to one **phase** by its ``op_name``:
``recompute`` where it passes ``rematted_computation`` (jax's name for what
a checkpointed block computes again in backward), else ``backward`` where
it passes a ``transpose(`` wrapper, else ``forward``. The ``ragged-dot``
kernels' own name says neither, so the rule leaves them ``forward``: a
recomputed grouped product is missing from ``recompute``.

A fusion bears its root's ``op_name``, so what XLA fuses across a scope's
boundary goes to one side whole. Own intervals of one chip do not overlap,
so **the buckets add up to the whole**: their sum is the time under
``tm.fwd_bwd`` as ``scopes.by_scope`` reads it (a union) plus the
``ragged-dot`` kernels, to the microsecond, and ``by_bucket`` asserts it.
(An asynchronous collective under ``tm.fwd_bwd`` keeps its whole interval
and would break the sum; no layout the benchmark runs has one there.)

A trace with no operation under ``tm.fwd_bwd`` gives None. A program whose
model opens no scope (the parent of the PR that added this file) reads
everything ``unnamed``, and a reader of one bucket gives None there.
"""

from __future__ import annotations

import functools
import json
import re
from collections import defaultdict

from benchmark import scopes, xplane

BUCKET = re.compile(r"tm\.(?:lm|attn|moe)\.[A-Za-z0-9_]+")
GROUPED_PRODUCT = "ragged-dot"  # XLA's kernel for lax.ragged_dot
FWD_BWD = "tm.fwd_bwd"
UNNAMED = "unnamed"
PHASES = ("forward", "recompute", "backward")


def bucket_of(op_name: str):
    """The one bucket of an operation, or None where it is no part of
    forward and backward."""
    if op_name.startswith(GROUPED_PRODUCT):
        return "tm.moe.experts"
    if scopes.scope_of(op_name) != FWD_BWD:
        return None
    found = BUCKET.findall(op_name)
    return found[-1] if found else UNNAMED


def phase_of(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "recompute"
    return "backward" if "transpose(" in op_name else "forward"


def divide(events, table) -> tuple:
    """One chip's events (``xplane.Trace.ops`` of a plane) and its ``{event
    name: op_name}``: ({bucket: {phase: seconds}}, the seconds of the
    ``ragged-dot`` kernels among them, {(event, op_name): seconds} of the
    ``unnamed`` operations)."""
    buckets = defaultdict(lambda: dict.fromkeys(PHASES, 0.0))
    unnamed, grouped = defaultdict(float), 0.0
    for name, pieces in scopes.own_intervals(events):
        op = table.get(name, "")
        bucket = bucket_of(op)
        if bucket is None:
            continue
        seconds = xplane.length(pieces)
        buckets[bucket][phase_of(op)] += seconds
        if op.startswith(GROUPED_PRODUCT):
            grouped += seconds
        if bucket == UNNAMED:
            unnamed[name, op] += seconds
    return buckets, grouped, unnamed


@functools.lru_cache(maxsize=4)
def by_bucket(path: str):
    """One trace (the directory ``start_trace`` wrote, or the ``.pb``),
    averaged over its chips: {"bucket_s": {bucket: {phase: seconds}},
    "whole_s": their sum, "unnamed_ops": the five longest ``unnamed``
    operations [((event, op_name), seconds)], "steps"}. None where nothing
    ran under ``tm.fwd_bwd``."""
    outer = scopes.by_scope(str(path))
    if outer is None or FWD_BWD not in outer["scope_s"]:
        return None
    file = path if str(path).endswith(".pb") else xplane.find(path)
    trace = xplane.load(file)
    names = scopes.op_names(file)
    n = len(trace.ops)
    bucket_s = defaultdict(lambda: dict.fromkeys(PHASES, 0.0))
    unnamed_ops, grouped_s = defaultdict(float), 0.0
    for plane, events in trace.ops.items():
        buckets, grouped, unnamed = divide(events, names.get(plane, {}))
        for bucket, phases in buckets.items():
            for phase, seconds in phases.items():
                bucket_s[bucket][phase] += seconds / n
        for key, seconds in unnamed.items():
            unnamed_ops[key] += seconds / n
        grouped_s += grouped / n
    whole = sum(sum(phases.values()) for phases in bucket_s.values())
    expected = outer["scope_s"][FWD_BWD] + grouped_s
    assert abs(whole - expected) < 1e-6, (
        f"the buckets hold {whole!r} s where tm.fwd_bwd and the ragged-dot "
        f"kernels hold {expected!r} s: an operation was counted twice or "
        "left out")
    return {
        "bucket_s": {k: dict(v) for k, v in bucket_s.items()},
        "whole_s": whole,
        "unnamed_ops": sorted(
            unnamed_ops.items(), key=lambda kv: -kv[1])[:5],
        "steps": outer["steps"],
    }


def _steady(run):
    """(the steady trace's buckets, its steps) or (None, None)."""
    path, _ = run["phase"]["traces"]["steady"]
    found = by_bucket(str(path))
    if found is None:
        return None, None
    steps = run["phase"].get("traced_steps") or found["steps"]
    return (found, steps) if steps else (None, None)


def bucket_ms_per_step(run, *buckets: str):
    """Milliseconds a step of the steady trace spends in ``buckets``, all
    phases; None where the program has none of them."""
    found, steps = _steady(run)
    if found is None:
        return None
    held = [found["bucket_s"][b] for b in buckets if b in found["bucket_s"]]
    if not held:
        return None
    return 1e3 * sum(sum(phases.values()) for phases in held) / steps


def phase_ms_per_step(run, phase: str):
    """Milliseconds a step spends in ``phase``, over every bucket."""
    found, steps = _steady(run)
    if found is None:
        return None
    return 1e3 * sum(
        phases[phase] for phases in found["bucket_s"].values()) / steps


def unnamed_share(run):
    """``unnamed`` over the buckets' sum, in percent; logs the division."""
    found, steps = _steady(run)
    if found is None:
        return None
    ms = lambda seconds: round(1e3 * seconds / steps, 3)  # noqa: E731
    scopes.log(
        "fwd_bwd ms per step by inner scope and phase: " + json.dumps({
            **{bucket: [ms(phases[p]) for p in PHASES]
               for bucket, phases in sorted(found["bucket_s"].items())},
            "phases": list(PHASES), "whole": ms(found["whole_s"]),
            "unnamed_ops": [[event[:80], op, ms(seconds)] for (event, op),
                            seconds in found["unnamed_ops"]]}))
    unnamed = found["bucket_s"].get(UNNAMED, {})
    return 100.0 * sum(unnamed.values()) / found["whole_s"]
