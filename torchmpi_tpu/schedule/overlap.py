"""Gradient-overlap scheduling: bucket flush order as a plan property.

The eager bucketed gradient path (:class:`~torchmpi_tpu.nn.
GradientBuckets`) partitions leaves in reverse-layer order — bucket 0
holds the LAST layers, whose gradients exist first during the backward
pass. This module decides *when* each bucket's collective launches
relative to the others, the classic compute/communication-overlap lever
("Scalable Distributed DNN Training using TensorFlow and CUDA-Aware
MPI", PAPERS.md):

- ``'reverse'`` — dispatch every bucket async in reverse-layer order
  the moment it is packed, wait in reverse launch order
  (``nn.lua:207-212``): bucket k's wire time overlaps bucket k+1's
  quantize/pack, and the dispatch ordinal is stamped into the schedule
  IR as a plan *priority* (:func:`~.ir.prioritized`) so tooling can
  tell a scheduled flush from its unscheduled twin.
- ``'none'`` — the all-at-once baseline: every bucket is packed (and
  the packs drained) before the FIRST dispatch, then each bucket
  dispatches and waits serially. Same collectives, same numerics —
  just zero overlap.

Both paths run the identical per-bucket allreduce on identical packed
payloads, so results are bitwise-identical scheduler off vs on — the
scheduler moves time, not bits.

Each scheduled flush records one flight-recorder sub-entry per bucket
on the rank-local ``"chunks"`` stream (the :class:`~.pipeline.
ChunkPipeline` convention — excluded from cross-rank desync diffs and
calibration extraction), stamped ``plan=overlap-<schedule>:<tag>#<b>``
spanning dispatch -> wait. PR 18's overlap ledger
(:func:`~torchmpi_tpu.telemetry.criticalpath.overlap_ledger`) then
*measures* the realized overlap fraction per schedule: disjoint spans
('none') read ~0, overlapped spans ('reverse') read toward
``1 - 1/num_buckets`` (``scripts/overlap_smoke.py`` checks the two rows
on two processes; ``tests/test_nn.py`` that the bits do not move).
"""

from __future__ import annotations

from typing import Any, List, Optional

from .. import constants
from ..telemetry import flightrecorder as _flight
from .pipeline import CHUNK_COMM, CHUNK_ROUTING

#: recognized bucket flush orders (the ``overlap_schedule`` knob)
SCHEDULES = ("none", "reverse")


def resolve_schedule(explicit: Optional[str] = None) -> str:
    """The flush-order decision for one bucketed sync: the explicit
    argument wins, else the ``overlap_schedule`` constant."""
    sched = explicit if explicit is not None else constants.get(
        "overlap_schedule"
    )
    if sched in (None, "", "none"):
        return "none"
    if sched not in SCHEDULES:
        raise ValueError(
            f"unknown overlap_schedule {sched!r}; expected one of "
            f"{SCHEDULES}"
        )
    return sched


def schedule_base(schedule: str, tag: str) -> str:
    """The ledger grouping id of one scheduled flush: every bucket's
    sub-entry is ``<base>#<bucket>``, so the overlap ledger folds the
    flush into ONE row keyed by schedule and tag."""
    return f"overlap-{schedule}:{tag}"


def register_priorities(bkts, comm, backend: Optional[str],
                        wire_dtype: Optional[str]) -> List[str]:
    """Stamp the reverse-layer flush order into the schedule IR.

    Compiles each bucket's plan (memoized — the same decision the
    dispatch replays) and registers a :func:`~.ir.prioritized` twin
    carrying the dispatch ordinal, so ``plan_by_id`` / ``--explain``
    can surface the order the scheduler chose. Returns the prioritized
    plan_ids (empty string where compilation was not possible — e.g.
    an op the compiler cannot price offline); registration is
    best-effort metadata, never a dispatch dependency."""
    from . import compiler as _compiler
    from . import ir as _ir

    if backend is None:
        # mirror collectives._dispatch's memoized selector choice when
        # it has already run; before the first dispatch the registered
        # twin just reflects the default route
        cache = getattr(comm, "_selector_cache", None) or {}
        backend = cache.get(("allreduce", "async")) or "xla"
    ids: List[str] = []
    for b in range(bkts.num_buckets):
        try:
            total = int(sum(bkts.sizes[i] for i in bkts.buckets[b]))
            ep = _compiler.compile_collective(
                "allreduce", (comm.size, total), bkts.bucket_dtype(b),
                comm, backend=backend, wire_dtype=wire_dtype,
            )
            twin = _ir.prioritized(ep.plan, b)
            _compiler._register_plans([twin])
            ids.append(twin.plan_id)
        except Exception:
            ids.append("")
    return ids


def _open_entry(base: str, b: int, buf) -> Optional[Any]:
    if not _flight.enabled():
        return None
    nbytes = int(buf.size) * buf.dtype.itemsize
    return _flight.recorder.record(
        CHUNK_COMM, "allreduce", payload=f"{nbytes}B",
        routing=CHUNK_ROUTING, plan=f"{base}#{b}",
    )


def run_bucketed_sync(
    bkts,
    grads,
    comm,
    backend: Optional[str] = None,
    wire_dtype: Optional[str] = None,
    average: bool = False,
    schedule: Optional[str] = None,
    tag: str = "grads",
):
    """One synchronous bucketed gradient sync under a flush schedule.

    ``bkts`` is a :class:`~torchmpi_tpu.nn.GradientBuckets`; ``grads``
    the rank-stacked gradient pytree it was built for. Returns the
    synced tree (``average`` divides by world size). ``tag`` names the
    flush in the overlap ledger (one row per (schedule, tag))."""
    import jax
    from jax import tree_util

    sched = resolve_schedule(schedule)
    p = comm.size
    leaves = tree_util.tree_leaves(grads)
    base = schedule_base(sched, tag)
    nb = bkts.num_buckets
    results: List[Any] = [None] * nb

    if sched == "reverse":
        register_priorities(bkts, comm, backend, wire_dtype)
        entries: List[Any] = [None] * nb
        handles: List[Any] = [None] * nb
        for b in range(nb):
            key, buf = bkts._packed_bucket(b, leaves, p, wire_dtype)
            entries[b] = _open_entry(base, b, buf)
            try:
                handles[b] = bkts._dispatch_bucket(
                    b, key, buf, comm, backend, wire_dtype
                )
            except BaseException:
                if entries[b] is not None:
                    _flight.FlightRecorder.fail(entries[b])
                raise
        # wait in reverse launch order: bucket nb-1 (the FIRST layers,
        # dispatched last) completes the flush; each sub-entry spans
        # dispatch -> wait, so the ledger sees the overlapped window
        for b in range(nb - 1, -1, -1):
            try:
                results[b] = handles[b].wait()
            except BaseException:
                if entries[b] is not None:
                    _flight.FlightRecorder.fail(entries[b])
                raise
            if entries[b] is not None:
                _flight.FlightRecorder.complete(entries[b])
    else:
        # all-at-once baseline: every bucket packed (and drained) before
        # the first dispatch, then dispatch+wait serially — the
        # pre-scheduler shape, kept as the ledger's comparison row
        packed = [
            bkts._packed_bucket(b, leaves, p, wire_dtype)
            for b in range(nb)
        ]
        jax.block_until_ready([buf for _, buf in packed])
        for b, (key, buf) in enumerate(packed):
            entry = _open_entry(base, b, buf)
            try:
                h = bkts._dispatch_bucket(
                    b, key, buf, comm, backend, wire_dtype
                )
                results[b] = h.wait()
            except BaseException:
                if entry is not None:
                    _flight.FlightRecorder.fail(entry)
                raise
            if entry is not None:
                _flight.FlightRecorder.complete(entry)

    bkts._launch_comm = comm
    return bkts.unflatten_results(grads, results, average=average, p=p)


__all__ = [
    "SCHEDULES",
    "register_priorities",
    "resolve_schedule",
    "run_bucketed_sync",
    "schedule_base",
]
