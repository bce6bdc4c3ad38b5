"""The comparison that decides ``correct``.

Numbers compared, each with a limit of its own from the configuration's
file: every followed loss, the first-moment buffer of the optimizer (after
one step it is the first gradient as the optimizer got it), the
parameters' change over the followed steps and, where the model keeps
running statistics (batch norm's averages), their change. The last three
are norms taken leaf by leaf: the gap between the program's norm and the
reference's, not the norm of a difference, against the reference's norm of
that leaf or of the median leaf, whichever is larger (some gradients are
all but zero), judged by the worst leaf or by the median leaf.
"""

from __future__ import annotations

import math

import jax
import numpy as np

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLedger:
    """What jax compiled, or loaded from the persistent cache, in this
    process, from jax.monitoring's own events."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == _BACKEND_COMPILE:
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    @property
    def programs(self) -> int:
        return self.compiles + self.cache_hits


def leaf_gaps(program, reference):
    """(gaps, leaf names) over two trees of per-leaf norms."""
    names = [
        "/".join(str(getattr(k, "key", k)) for k in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(reference)[0]
    ]
    r = np.array(jax.tree_util.tree_leaves(reference), np.float64)
    p = np.array(jax.tree_util.tree_leaves(program), np.float64)
    if r.shape != p.shape:
        raise ValueError(f"{p.shape} program leaves, {r.shape} reference")
    # where most leaves' norms are exactly zero (a block that starts as the
    # identity passes no gradient inside), the median is of the others
    floor = np.median(r[r > 0]) if np.any(r > 0) else 1.0
    gaps = np.abs(p - r) / np.maximum(r, floor)
    return np.where(np.isfinite(gaps), gaps, np.inf), names


TREES = {"moment_norm_gap": "moment_norms", "update_norm_gap": "update_norms",
         "stat_norm_gap": "stat_norms"}


def compare(program: dict, reference: dict) -> dict:
    """The numbers that can be compared, by name. ``program`` and
    ``reference`` hold ``losses`` (lists) and the trees of ``TREES``;
    ``stat_norms`` is None for a model that keeps no running statistics.
    Each tree gives its worst leaf's gap and, as ``<name>_median``, the
    median leaf's: a worst leaf swings from seed to seed, the median leaf
    does not. A configuration's ``limits`` say which of them it judges."""
    pl = np.array(program["losses"], np.float64)
    rl = np.array(reference["losses"], np.float64)
    loss_gap = float(np.max(np.abs(pl - rl) / np.abs(rl)))
    if not math.isfinite(loss_gap):
        loss_gap = math.inf
    numbers = {"loss_gap": (loss_gap, "worst of the followed losses")}
    for name, tree in TREES.items():
        if reference.get(tree) is None:
            continue
        gaps, names = leaf_gaps(program[tree], reference[tree])
        worst = int(np.argmax(gaps))
        numbers[name] = (float(gaps[worst]), names[worst])
        numbers[name + "_median"] = (
            float(np.median(gaps)), f"of {len(gaps)} leaves")
    return numbers


def follow_reference(config: str, cfg: dict, params, mode, batches,
                     precision: str = "float32") -> dict:
    """The plain reference of ``config`` over the followed ``batches`` from
    ``params``, shaped as ``mode`` shows its own steps (a resident epoch
    shows its mean and last loss only)."""
    from benchmark import configs, reference

    got = configs.load_module(reference.HERE / f"{config}.py").follow(
        cfg, params, batches, groups=mode.chips, precision=precision,
        moment_after=mode.moment_after)
    got["losses"] = mode.reference_losses(got["losses"])
    return got


def verdict(numbers: dict, limits: dict, out=print) -> bool:
    """Print each number that has a limit beside it; true when all are
    inside. A limit with no number to hold is an error."""
    missing = sorted(set(limits) - set(numbers))
    if missing:
        raise KeyError(f"limits for numbers nothing computed: {missing}")
    ok = True
    for name, limit in limits.items():
        value, where = numbers[name], ""
        if isinstance(value, tuple):
            value, where = value
        inside = value <= limit if math.isfinite(value) else False
        ok = ok and inside
        out(f"# check {name} {value:.6g} limit {limit:g} "
            f"{'ok' if inside else 'OUTSIDE'} {where}".rstrip())
    return ok
