"""Coalescing dispatch for the eager latency path.

The MNIST north-star is *latency-bound*: a step issues one eager
``run()``/``run_async()`` per gradient bucket, each paying Python-side
hashing, cache lookup and dispatch. GC3 (arXiv:2201.11840) compiles
collective *plans* once and replays them; the TF/CUDA-aware-MPI
characterization (arXiv:1810.11112) shows small-tensor coalescing into a
fused buffer is the biggest lever for latency-bound data-parallel
training. This module is both, for the eager surface:

- :class:`FusionBuffer` packs pending same-``(op, dtype, wire, backend)``
  async collectives into ONE contiguous flat buffer and flushes them as a
  *single* allreduce / reduce-scatter when the pending per-rank payload
  reaches ``fusion_buffer_bytes``, or on ``wait()`` / ``sync_all()``.
- A flush is ONE XLA dispatch: ``eager.run_fused`` compiles
  pack-concat + collective into a single plan per (layout, dtype,
  routing) and replays it — not k dispatches, not even pack + collective
  = 2. (The eager ``GradientBuckets`` path keeps its own persistent
  *donated* flat buffers — the ``BlockSequential.lua:29-89``
  flatten-once idiom — because its per-bucket handles are part of the
  public API.)
- Caller tensors are only ever *read* (copied into the fused buffer);
  donation never touches a live gradient.

``fusion_min_tensors`` guards the degenerate case: a flush holding fewer
tensors than that dispatches them unfused (packing one tensor buys
nothing). ``fusion_buffer_bytes = 0`` disables coalescing entirely —
every submit dispatches immediately, the pre-fusion behavior.

Telemetry (when enabled): tensors coalesced, flushes by reason
(``bytes`` / ``wait`` / ``explicit``), and fused-vs-unfused dispatch
latency histograms.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import constants, telemetry as _telemetry
from ..runtime.communicator import Communicator
from ..runtime.handles import SyncHandle, handles
from ..telemetry import flightrecorder as _flight
from . import eager

# ops the fusion layer understands; everything else passes through
_FUSABLE = ("allreduce", "reducescatter")

_MET = None


def _metric_handles():
    global _MET
    if _MET is None:
        m = _telemetry.metrics
        _MET = (
            m.counter(
                "tm_fusion_tensors_total",
                "tensors entering the fusion layer by op/wire/path "
                "(path=fused: coalesced into a flat buffer; "
                "path=unfused: dispatched individually)",
            ),
            m.counter(
                "tm_fusion_flushes_total",
                "fusion-buffer flushes by op/reason "
                "(bytes=capacity, wait=handle drain, explicit=flush_all)",
            ),
            m.histogram(
                "tm_fusion_dispatch_seconds",
                "host-side dispatch wall time per flush by op/path (fused "
                "against unfused)",
            ),
        )
    return _MET


def count_coalesced(op: str, wire, n: int, path: str = "fused") -> None:
    """Feed the coalescing counters from packing done OUTSIDE the
    FusionBuffer (e.g. ``GradientBuckets``' persistent flat buffers)."""
    if _telemetry.enabled() and n:
        tensors, _, _ = _metric_handles()
        tensors.inc(n, op=op, wire=wire or "auto", path=path)


class FusionHandle(SyncHandle):
    """Handle for one tensor submitted to a :class:`FusionBuffer`.

    ``wait()`` forces the owning group's flush (reason ``wait``) if it has
    not flushed yet, then slices this tensor's segment out of the fused
    result. Registered in the global handle table under kind ``"fusion"``
    — NOT ``"collective"``: ``sync_all()`` (and thus ``stop()``) drains
    every kind, but ``run_async``'s in-flight backpressure only drains
    ``"collective"`` handles, so a below-threshold flush that dispatches
    unfused through ``run_async`` can never be handed one of its own
    group's handles mid-flush (re-entrant double dispatch). A pending
    fused submission is not an in-flight collective anyway."""

    __slots__ = ("_group", "_idx")

    def __init__(self, group: "_PendingGroup", idx: int):
        # the arrays slot is a placeholder: wait() is fully overridden
        super().__init__(arrays=())
        self._group = group
        self._idx = idx

    def wait(self):
        if self._done:
            return self._result
        out = self._group.result_for(self._idx)
        self._result = jax.block_until_ready(out)
        self._done = True
        if self._table_index is not None:
            handles._discard(self._table_index)
            self._table_index = None
        return self._result

    @property
    def done(self) -> bool:
        return self._done


class _PendingGroup:
    """Tensors awaiting one fused dispatch: same (op, dtype, wire,
    backend), each flattened to a [p, n] slab at a recorded offset."""

    def __init__(self, buffer: "FusionBuffer", key: Tuple, op: str, dtype,
                 wire, backend):
        self.buffer = buffer
        self.key = key
        self.op = op
        self.dtype = dtype
        self.itemsize = jnp.dtype(dtype).itemsize
        self.wire = wire
        self.backend = backend
        self.segments: List[Tuple[int, Tuple[int, ...]]] = []  # (n, shape)
        self.flats: List = []
        self.total = 0
        self._results: Optional[List] = None
        self._fused_buf = None

    def add(self, flat, shape) -> int:
        idx = len(self.segments)
        self.segments.append((int(flat.shape[1]), tuple(shape)))
        self.flats.append(flat)
        self.total += int(flat.shape[1])
        return idx

    @property
    def pending_bytes(self) -> int:
        return self.total * self.itemsize

    def flushed(self) -> bool:
        return self._results is not None or self._fused_buf is not None

    def result_for(self, idx: int):
        if not self.flushed():
            self.buffer._flush_group(self, reason="wait")
        if self._results is not None:
            r = self._results[idx]
            if isinstance(r, SyncHandle):
                r = self._results[idx] = r.wait()
            return r
        n, shape = self.segments[idx]
        off = sum(s[0] for s in self.segments[:idx])
        if self.op == "reducescatter":
            # interleaved packing (see _flush_group): rank r's fused block
            # holds each tensor's r-th scatter chunk contiguously, so the
            # segment comes back out by offset/p and the scattered shape
            # keeps every dim but the last, which shrank by p
            p = self.buffer.comm.size
            seg = self._fused_buf[:, off // p : (off + n) // p]
            return seg.reshape(shape[:-1] + (shape[-1] // p,))
        return self._fused_buf[:, off : off + n].reshape(shape)


class FusionBuffer:
    """Per-communicator coalescing dispatcher for eager async collectives.

    Obtain via :func:`get_fusion_buffer` (cached on the communicator, torn
    down by ``free_collective_resources``). ``submit()`` is the drop-in
    replacement for ``eager.run_async``: it returns a handle immediately;
    the collective itself launches when the buffer fills or the handle is
    waited."""

    def __init__(self, comm: Communicator):
        self.comm = comm
        self._groups: Dict[Tuple, _PendingGroup] = {}

    # ------------------------------------------------------------------
    def submit(
        self,
        op: str,
        x,
        wire_dtype: Optional[str] = None,
        backend: Optional[str] = None,
    ) -> SyncHandle:
        """Queue one rank-stacked tensor for a fused ``op``; returns a
        handle. Falls through to an immediate unfused async dispatch when
        coalescing cannot engage (disabled, unfusable op, or a
        reducescatter whose last dim does not divide by the world size)."""
        if not isinstance(x, jax.Array):
            x = jnp.asarray(x)
        cap = constants.get("fusion_buffer_bytes")
        fusable = (
            cap > 0
            and op in _FUSABLE
            and x.ndim >= 2
            and x.shape[0] == self.comm.size
            and not (
                op == "reducescatter"
                and (x.ndim != 2 or x.shape[-1] % self.comm.size)
            )
        )
        if not fusable:
            self._count_tensor(op, wire_dtype, "unfused")
            return self._dispatch_unfused(op, x, wire_dtype, backend)
        dtype = x.dtype
        key = (op, dtype, wire_dtype, backend)
        group = self._groups.get(key)
        if group is None:
            group = self._groups[key] = _PendingGroup(
                self, key, op, dtype, wire_dtype, backend
            )
        # reshape only when needed: a [p, n] tensor (the gradient-bucket
        # shape) skips the per-submit dispatch entirely
        flat = x if x.ndim == 2 else jnp.reshape(x, (self.comm.size, -1))
        group.add(flat, x.shape)
        h = FusionHandle(group, len(group.segments) - 1)
        handles.register(h, kind="fusion")
        if group.pending_bytes >= cap:
            self._flush_group(group, reason="bytes")
        return h

    def flush_all(self, reason: str = "explicit") -> None:
        """Dispatch every pending group now (handles stay waitable).

        Under ``overlap_schedule='reverse'`` groups flush in REVERSE
        insertion order: gradient producers submit forward-layer-first,
        so the reverse order puts the last layers — the first gradients
        ready during backward — on the wire first (the same flush order
        the bucket scheduler dispatches, ``schedule/overlap.py``)."""
        groups = list(self._groups.values())
        if constants.get("overlap_schedule") == "reverse":
            groups.reverse()
        for group in groups:
            if not group.flushed():
                self._flush_group(group, reason=reason)

    def flush_for(self, submitted, reason: str = "wait") -> None:
        """Dispatch only the pending groups the given handles belong to —
        a caller synchronizing ITS tensors must not cut short the
        capacity window of unrelated submitters sharing the buffer."""
        seen = set()
        for h in submitted:
            group = getattr(h, "_group", None)
            if group is not None and id(group) not in seen:
                seen.add(id(group))
                if not group.flushed():
                    self._flush_group(group, reason=reason)

    @property
    def pending_tensors(self) -> int:
        return sum(len(g.segments) for g in self._groups.values())

    # ------------------------------------------------------------------
    def _count_tensor(self, op, wire, path, n: int = 1) -> None:
        if _telemetry.enabled():
            tensors, _, _ = _metric_handles()
            tensors.inc(n, op=op, wire=wire or "auto", path=path)

    def _dispatch_unfused(self, op, x, wire_dtype, backend):
        # route like the public namespace (selector-decided backend when
        # none was pinned); local import breaks the package cycle
        from . import _dispatch as _ns_dispatch

        t0 = time.perf_counter()
        kw = {"wire_dtype": wire_dtype} if op in eager._WIRE_OPS else {}
        h = _ns_dispatch(op, x, self.comm, "async", backend, **kw)
        if _telemetry.enabled():
            _, _, lat = _metric_handles()
            lat.observe(time.perf_counter() - t0, op=op, path="unfused")
        return h

    def _flush_group(self, group: _PendingGroup, reason: str) -> None:
        self._groups.pop(group.key, None)
        telemetry_on = _telemetry.enabled()
        if telemetry_on:
            _, flushes, lat = _metric_handles()
            flushes.inc(op=group.op, reason=reason)
        flight_entry = None
        if _flight.enabled():
            # the flush event itself joins the comm's flight stream (the
            # dispatch it triggers records separately via eager): a
            # cross-rank layout mismatch here IS a desync even when the
            # per-tensor dispatches happen to agree
            flight_entry = _flight.recorder.record(
                _flight.comm_key(self.comm), f"fusion.{group.op}",
                payload=(tuple(n for n, _ in group.segments), group.dtype),
                wire=group.wire or "auto", backend=group.backend or "auto",
                routing=reason,
            )
        if len(group.segments) < max(1, constants.get("fusion_min_tensors")):
            # packing below the threshold costs more than it saves:
            # dispatch each tensor individually (handles index into the
            # per-segment results list)
            self._count_tensor(
                group.op, group.wire, "unfused", len(group.segments)
            )
            try:
                group._results = [
                    self._dispatch_unfused(
                        group.op, flat.reshape(shape), group.wire,
                        group.backend
                    )
                    for flat, (_, shape) in zip(group.flats, group.segments)
                ]
            except BaseException:
                if flight_entry is not None:
                    _flight.FlightRecorder.fail(flight_entry)
                raise
            group.flats = []
            if flight_entry is not None:
                _flight.FlightRecorder.complete(flight_entry)
            return
        self._count_tensor(
            group.op, group.wire, "fused", len(group.segments)
        )
        t0 = time.perf_counter()
        ns = tuple(n for n, _ in group.segments)
        from . import _dispatch as _ns_dispatch

        try:
            out = self._dispatch_fused(group, ns, _ns_dispatch)
        except BaseException:
            if flight_entry is not None:
                _flight.FlightRecorder.fail(flight_entry)
            raise
        if flight_entry is not None:
            _flight.FlightRecorder.complete(flight_entry)
        if telemetry_on:
            lat.observe(time.perf_counter() - t0, op=group.op, path="fused")
        group._fused_buf = (
            out.reshape(self.comm.size, -1) if out.ndim != 2 else out
        )

    def _dispatch_fused(self, group: _PendingGroup, ns, _ns_dispatch):
        if group.op == "reducescatter":
            # interleave so rank r's scattered block holds every tensor's
            # r-th chunk: [p, n_i] -> [p, p, n_i/p], concat chunk axes,
            # flatten back to [p, total] (each n_i divides by p — gated
            # at submit)
            p = self.comm.size
            parts = [
                f.reshape(p, p, n // p) for f, n in zip(group.flats, ns)
            ]
            buf = jnp.concatenate(parts, axis=2).reshape(p, -1)
            group.flats = []
            out = _ns_dispatch(
                group.op, buf, self.comm, "sync", group.backend,
                wire_dtype=group.wire,
            )
        else:
            # allreduce: pack + reduce as ONE compiled plan (run_fused) —
            # a flush of k tensors is a single XLA dispatch
            flats, group.flats = group.flats, []
            out = _ns_dispatch(
                group.op, flats, self.comm, "fused", group.backend,
                wire_dtype=group.wire,
            )
        return out


def get_fusion_buffer(comm: Optional[Communicator] = None) -> FusionBuffer:
    """The communicator's coalescing dispatcher (lazily attached, like the
    executable cache; dropped by ``free_collective_resources``)."""
    if comm is None:
        from .. import runtime_state

        comm = runtime_state.current_communicator()
    fb = getattr(comm, "_fusion_buffer", None)
    if fb is None:
        fb = FusionBuffer(comm)
        comm._fusion_buffer = fb  # type: ignore[attr-defined]
    return fb
