"""NN integration: parameter/gradient synchronization over pytrees.

TPU-native analog of ``torchmpi/nn.lua``:

- :func:`synchronize_parameters` — one-shot parameter sync before training:
  broadcast from rank 0, or allreduce + divide (``nn.lua:32-46``).
- :func:`synchronize_gradients` — sum-allreduce every gradient leaf
  (``nn.lua:49-56``). Sum, not mean, matching the reference; pass
  ``average=True`` to divide.
- The overlapped path. The reference monkey-patches each module's
  ``backward`` to launch an async allreduce per layer on a fenced stream
  (``nn.lua:112-213``); on TPU the latency-hiding belongs to XLA's
  async-collective scheduler, so the REAL backward-compute overlap lives in
  the **in-graph path** (``in_graph_synchronize_gradients``, compiled by
  the engine): XLA groups the leaves' psums and places them against the
  remaining compute. The *eager* :class:`GradientBuckets` API
  (≙ ``BlockSequential``'s equal-parameter-count partitioning,
  ``BlockSequential.lua:29-89``) launches only after the full gradient tree
  exists — its buckets overlap with EACH OTHER and with whatever host/device
  work follows the launch, not with the backward that produced them; handles
  are waited in reverse order (``nn.lua:207-212``).
- In-graph variants (``in_graph_*``) for use inside jit/shard_map — the
  idiomatic path the engine compiles.

Eager functions take rank-stacked pytrees: every leaf has leading axis
``comm.size`` (rank r's values at index r).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, tree_util

from .. import collectives, telemetry as _telemetry
from ..collectives import eager, primitives as _prim
from ..runtime.communicator import Communicator
from ..runtime.handles import SyncHandle
from ..telemetry import names as _names


def _comm(comm: Optional[Communicator]) -> Communicator:
    if comm is not None:
        return comm
    from .. import runtime_state

    return runtime_state.current_communicator()


# ---------------------------------------------------------------------------
# flatten/unflatten: single fused buffer per collective (the reason
# BlockSequential flattens each block via getParameters)
# ---------------------------------------------------------------------------


def _fused_apply(tree, p: int, sync_one: Callable):
    """Apply ``sync_one`` to one fused [p, total] buffer per dtype group.

    Grouping by dtype (instead of casting everything through float32)
    preserves integer leaves exactly and float64 precision while still
    issuing O(#dtypes) collectives rather than O(#leaves)."""
    leaves, treedef = tree_util.tree_flatten(tree)
    by_dtype: Dict = {}
    for i, l in enumerate(leaves):
        by_dtype.setdefault(jnp.result_type(l), []).append(i)
    out = list(leaves)
    for dtype, idxs in by_dtype.items():
        flats = [jnp.reshape(leaves[i], (p, -1)) for i in idxs]
        buf = sync_one(jnp.concatenate(flats, axis=1))
        off = 0
        for i in idxs:
            n = int(np.prod(leaves[i].shape[1:]))
            out[i] = jnp.reshape(buf[:, off : off + n], leaves[i].shape).astype(
                dtype
            )
            off += n
    return tree_util.tree_unflatten(treedef, out)


def _flatten_stacked(tree, p: int):
    """Concat rank-stacked leaves [p, ...] into one [p, total] buffer
    (float32; used by statistics-only paths like check_with_allreduce)."""
    leaves = tree_util.tree_leaves(tree)
    flats = [jnp.reshape(l, (p, -1)).astype(jnp.float32) for l in leaves]
    return jnp.concatenate(flats, axis=1) if flats else jnp.zeros((p, 0))


# ---------------------------------------------------------------------------
# eager pytree synchronization (nn.lua:32-56)
# ---------------------------------------------------------------------------


def synchronize_parameters(
    params,
    comm: Optional[Communicator] = None,
    with_allreduce: bool = False,
    root: int = 0,
    fused: bool = True,
):
    """Make every rank's parameters identical: broadcast from ``root`` or
    allreduce + divide by size (``nn.lua:32-46``)."""
    comm = _comm(comm)
    p = comm.size

    def sync_one(buf):
        if with_allreduce:
            return collectives.allreduce_tensor(buf, comm=comm) / p
        return collectives.broadcast_tensor(buf, root=root, comm=comm)

    if fused:
        return _fused_apply(params, p, sync_one)
    return tree_util.tree_map(sync_one, params)


def synchronize_gradients(
    grads,
    comm: Optional[Communicator] = None,
    average: bool = False,
    fused: bool = True,
    wire_dtype: Optional[str] = None,
):
    """Sum-allreduce every gradient leaf (``nn.lua:49-56``).

    ``wire_dtype`` ('full' | 'bf16' | 'int8'; None = constants default)
    selects the on-wire encoding for the bandwidth-path allreduce —
    int8 ships block-quantized gradients with f32 accumulation (EQuARX-
    style), engaging only for f32 buffers above the tuned cutoff. Integer
    leaves always travel uncompressed (their dtype group resolves to
    'full').

    ``fused=True`` routes through the communicator's coalescing
    :class:`~torchmpi_tpu.collectives.fusion.FusionBuffer` (when
    ``fusion_buffer_bytes`` > 0): every leaf is submitted individually,
    packed into one persistent donated flat buffer per dtype, and shipped
    as a SINGLE allreduce per dtype group — same collective count as the
    old host-side concat, but the pack is a cached executable reusing the
    previous call's device memory, and the coalescing telemetry sees it."""
    comm = _comm(comm)
    p = comm.size

    from .. import constants as _constants

    if fused and _constants.get("fusion_buffer_bytes") > 0:
        from ..collectives.fusion import get_fusion_buffer

        fb = get_fusion_buffer(comm)
        leaves, treedef = tree_util.tree_flatten(grads)
        handles = [
            fb.submit(
                "allreduce",
                l if l.ndim == 2 else jnp.reshape(l, (p, -1)),
                wire_dtype=wire_dtype,
            )
            for l in leaves
        ]
        # one dispatch per dtype group, now — only OUR groups (other
        # callers' pending submits keep their capacity window)
        fb.flush_for(handles)
        out = []
        for l, h in zip(leaves, handles):
            buf = h.wait()
            if average:
                buf = (buf / p).astype(jnp.result_type(l))
            out.append(jnp.reshape(buf, l.shape))
        return tree_util.tree_unflatten(treedef, out)

    def sync_one(buf):
        out = collectives.allreduce_tensor(
            buf, comm=comm, wire_dtype=wire_dtype
        )
        return out / p if average else out

    if fused:
        return _fused_apply(grads, p, sync_one)
    return tree_util.tree_map(sync_one, grads)


# ---------------------------------------------------------------------------
# gradient buckets (BlockSequential.lua:29-89 partitioning)
# ---------------------------------------------------------------------------


class GradientBuckets:
    """Partition a pytree's leaves into ``num_buckets`` blocks of ~equal
    element count, in reverse-leaf order (gradients become available
    last-layer-first during backward, so reverse order lets bucket 0's
    collective launch earliest — the same motivation as the reference's
    per-block overlapped backward, ``BlockSequential.lua:114-151``)."""

    def __init__(self, params_template, num_buckets: int):
        leaves, self.treedef = tree_util.tree_flatten(params_template)
        self.shapes = [l.shape for l in leaves]
        self.sizes = [int(np.prod(l.shape)) for l in leaves]
        self.dtypes = [jnp.result_type(l) for l in leaves]
        total = sum(self.sizes)
        num_buckets = max(1, min(num_buckets, len(leaves)))
        target = total / num_buckets
        # Greedy contiguous partition over reversed leaf order.
        order = list(range(len(leaves)))[::-1]
        self.buckets: List[List[int]] = [[]]
        acc = 0
        for idx in order:
            if (
                acc >= target
                and len(self.buckets) < num_buckets
                and self.buckets[-1]
            ):
                self.buckets.append([])
                acc = 0
            self.buckets[-1].append(idx)
            acc += self.sizes[idx]
        self.num_buckets = len(self.buckets)
        # persistent flat-buffer state for the coalesced eager path: one
        # cached pack executable + recycled (donated) buffer per bucket
        self._pack_fns: Dict[int, Callable] = {}
        self._spares: Dict[int, Any] = {}
        # error-feedback state (wire_error_feedback): one cached encode
        # executable + persistent f32 residual buffer per bucket — the
        # quantization error of flush k is added back before flush k+1's
        # quantization (1-bit SGD/QSGD lineage)
        self._ef_fns: Dict[Any, Callable] = {}
        self._residuals: Dict[Any, Any] = {}

    def bucket_leaves(self, tree, b: int):
        leaves = tree_util.tree_leaves(tree)
        return [leaves[i] for i in self.buckets[b]]

    def bucket_dtype(self, b: int):
        """The bucket's wire dtype: the promotion of its leaves (matches
        the concat the fused buffer ships)."""
        return jnp.result_type(*[self.dtypes[i] for i in self.buckets[b]])

    def _pack_bucket(self, b: int, flats, dtype):
        """Pack bucket ``b``'s flattened [p, w_i] leaves into its
        persistent flat [p, total] buffer via a cached jitted gather that
        DONATES the previous step's buffer — steady-state training
        re-packs into the same device memory with zero per-step concat
        allocation (the ``BlockSequential`` flatten-once idiom,
        ``BlockSequential.lua:29-89``). Caller leaves are only read,
        never donated."""
        p = flats[0].shape[0]
        widths = tuple(int(f.shape[1]) for f in flats)
        key = (b, widths, str(jnp.dtype(dtype)))
        fn = self._pack_fns.get(key)
        if fn is None:
            offsets = tuple(int(o) for o in np.cumsum((0,) + widths[:-1]))

            def pack(buf, *slabs):
                for off, slab in zip(offsets, slabs):
                    buf = jax.lax.dynamic_update_slice(
                        buf, slab.astype(buf.dtype), (0, off)
                    )
                return buf

            fn = jax.jit(pack, donate_argnums=(0,))
            self._pack_fns[key] = fn
        buf = self._spares.pop(key, None)
        if buf is None or getattr(buf, "is_deleted", lambda: False)():
            buf = jnp.zeros((p, sum(widths)), dtype)
        return key, fn(buf, *flats)

    def _packed_bucket(self, b: int, leaves, p: int,
                       wire_dtype: Optional[str] = None):
        """Pack bucket ``b``'s leaves into its flat [p, total] buffer;
        returns ``(key, buf)`` — ``key`` is the spare-recycling key of
        the persistent path (``fusion_buffer_bytes`` > 0), None on the
        fresh-concat fallback."""
        from .. import constants as _constants
        from ..collectives.fusion import count_coalesced

        flats = [jnp.reshape(leaves[i], (p, -1)) for i in self.buckets[b]]
        if _constants.get("fusion_buffer_bytes") > 0:
            key, buf = self._pack_bucket(b, flats, self.bucket_dtype(b))
            count_coalesced("allreduce", wire_dtype, len(flats))
            return key, buf
        return None, jnp.concatenate(flats, axis=1)

    def _error_feedback(self, b: int, buf, wire_dtype: Optional[str]):
        """Error-feedback encode of one packed bucket: add the stored
        residual, quantize+dequantize on exactly the wire's grid (per
        rank row, ``wire_quant_block_size`` blocks for int8; bf16
        round-trip for bf16), store the new residual, ship the
        quantized values. The wire re-quantizes them exactly on its
        first hop (the max block element maps to ±127·scale, so the
        scale — and hence every code — reproduces), which is what makes
        the residual the TRUE compression error. No-op whenever the
        wire would not engage (non-f32 bucket, below the cutoff,
        'full'). ``buf`` is donated; callers use the returned array."""
        from .. import constants as _constants

        p, n = int(buf.shape[0]), int(buf.shape[1])
        wire = eager.resolve_wire_dtype(
            "allreduce", n, jnp.result_type(buf), wire_dtype
        )
        if wire not in ("int8", "bf16"):
            return buf
        block = int(_constants.get("wire_quant_block_size"))
        fkey = (b, p, n, wire, block)
        fn = self._ef_fns.get(fkey)
        if fn is None:
            if wire == "bf16":
                def encode(raw, res):
                    comp = raw + res
                    qv = comp.astype(jnp.bfloat16).astype(jnp.float32)
                    return qv, comp - qv
            else:
                pad = -n % block

                def encode(raw, res):
                    comp = raw + res
                    padded = (
                        jnp.pad(comp, ((0, 0), (0, pad))) if pad else comp
                    )
                    blocks = padded.reshape(p, -1, block)
                    scale = jnp.maximum(
                        jnp.max(jnp.abs(blocks), axis=2, keepdims=True),
                        _prim._SCALE_FLOOR,
                    ) / 127.0
                    q = jnp.round(blocks / scale)
                    qv = (q * scale).reshape(p, -1)[:, :n]
                    return qv, comp - qv

            fn = jax.jit(encode, donate_argnums=(0, 1))
            self._ef_fns[fkey] = fn
        res = self._residuals.pop(fkey, None)
        if res is None or getattr(res, "is_deleted", lambda: False)():
            res = jnp.zeros((p, n), jnp.float32)
        qv, new_res = fn(buf, res)
        self._residuals[fkey] = new_res
        return qv

    def _dispatch_bucket(
        self,
        b: int,
        key,
        buf,
        comm: Communicator,
        backend: Optional[str],
        wire_dtype: Optional[str],
    ) -> SyncHandle:
        """Dispatch one packed bucket async (error-feedback encoding it
        first when ``wire_error_feedback`` engages) and recycle the
        in-flight buffer as next step's donated spare."""
        from .. import constants as _constants

        recycle = key is not None and not _constants.get(
            "donate_eager_buffers"
        )
        if _constants.get("wire_error_feedback"):
            buf = self._error_feedback(b, buf, wire_dtype)
        # one dispatch path for selector-routed AND pinned backends;
        # note a pinned backend is honored EXACTLY (no
        # ring_implementation remap — that applies only to
        # selector-routed calls)
        h = collectives._dispatch(
            "allreduce", buf, comm, "async", backend,
            wire_dtype=wire_dtype,
        )
        if recycle:
            # the collective did not consume the packed buffer: next
            # step's pack donates it (XLA orders the reuse after the
            # in-flight read)
            self._spares[key] = buf
        return h

    def allreduce_async(
        self,
        grads,
        comm: Optional[Communicator] = None,
        backend: Optional[str] = None,
        wire_dtype: Optional[str] = None,
    ) -> List[SyncHandle]:
        """Launch one async fused allreduce per bucket; returns handles in
        launch order (wait them in reverse, ``nn.lua:207-212``).
        ``backend`` optionally pins the collective backend (e.g. ``'ring'``
        to engage the hierarchical intra×inter composition on 2-level
        communicators); default = selector choice. ``wire_dtype`` selects
        the per-bucket wire encoding (:func:`synchronize_gradients`).

        With ``fusion_buffer_bytes`` > 0 (the default) each bucket packs
        into its persistent donated flat buffer (:meth:`_pack_bucket`) —
        no per-step concat allocation; 0 falls back to a fresh concat per
        launch (the pre-fusion behavior)."""
        comm = _comm(comm)
        p = comm.size
        leaves = tree_util.tree_leaves(grads)
        handles = []
        for b in range(self.num_buckets):
            key, buf = self._packed_bucket(b, leaves, p, wire_dtype)
            handles.append(
                self._dispatch_bucket(b, key, buf, comm, backend, wire_dtype)
            )
        # Remember which communicator these collectives ran on so the
        # averaging divisor in wait_and_unflatten defaults correctly.
        self._launch_comm = comm
        return handles

    def sync_scheduled(
        self,
        grads,
        comm: Optional[Communicator] = None,
        backend: Optional[str] = None,
        wire_dtype: Optional[str] = None,
        average: bool = False,
        schedule: Optional[str] = None,
        tag: str = "grads",
    ):
        """Synchronous bucketed allreduce under the overlap scheduler
        (:mod:`torchmpi_tpu.schedule.overlap`): ``schedule='reverse'``
        dispatches every bucket async in reverse-layer order before any
        wait (bucket k's wire time overlaps bucket k+1's quantize/pack),
        ``'none'`` is the all-at-once baseline; None reads the
        ``overlap_schedule`` constant. Same collectives either way —
        results are bitwise-identical scheduler off vs on. ``tag`` names
        the flush in the measured overlap ledger."""
        from ..schedule import overlap as _overlap

        return _overlap.run_bucketed_sync(
            self, grads, _comm(comm), backend=backend,
            wire_dtype=wire_dtype, average=average, schedule=schedule,
            tag=tag,
        )

    def wait_and_unflatten(
        self,
        grads,
        handles: Sequence[SyncHandle],
        average: bool = False,
        comm: Optional[Communicator] = None,
    ):
        """Wait handles (reverse order) and scatter results back to tree.
        ``average`` must be passed explicitly; the divisor defaults to the
        communicator the matching allreduce_async launched on."""
        if comm is None:
            comm = getattr(self, "_launch_comm", None)
        p = _comm(comm).size
        results = [None] * len(handles)
        for b in range(len(handles) - 1, -1, -1):
            results[b] = handles[b].wait()
        return self.unflatten_results(grads, results, average=average, p=p)

    def unflatten_results(self, grads, results, average: bool = False,
                          p: int = 1):
        """Scatter per-bucket reduced [p, total] buffers back into the
        tree (``average`` divides by ``p``)."""
        leaves = list(tree_util.tree_leaves(grads))
        for b, buf in enumerate(results):
            if average:
                buf = buf / p
            off = 0
            for i in self.buckets[b]:
                shape = leaves[i].shape  # rank-stacked [p, ...]
                n = int(np.prod(shape[1:]))
                leaves[i] = jnp.reshape(buf[:, off : off + n], shape)
                off += n
        return tree_util.tree_unflatten(self.treedef, leaves)


# ---------------------------------------------------------------------------
# in-graph variants (for jit/shard_map training steps)
# ---------------------------------------------------------------------------


def _note_sync(reduced) -> None:
    """Publish what one step's in-graph gradient sync reduces, from the
    static shapes of the buffers handed to the collective: called while a
    sync function is traced, so the gauges describe the step most recently
    traced (``tm_engine_sync_bytes_per_step``: bytes each rank
    contributes; ``tm_engine_sync_calls_per_step``: collectives)."""
    m = _telemetry.metrics
    m.gauge(
        "tm_engine_sync_bytes_per_step",
        "bytes each rank hands to the in-graph gradient sync per step "
        "(static shapes of the step most recently traced)",
    ).set(sum(int(np.prod(b.shape)) * b.dtype.itemsize for b in reduced))
    m.gauge(
        "tm_engine_sync_calls_per_step",
        "collectives the in-graph gradient sync issues per step (the "
        "step most recently traced)",
    ).set(len(reduced))


def _sync_leaves(leaves, idxs, n, axis):
    """Reduce the leaves ``idxs`` where they lie, in the shapes backward
    made them: one ``lax.psum`` over the list (no buffer to copy into or
    out of; grouping the all-reduces and placing them against backward
    is XLA's) and, for a mean, each leaf divided by ``n`` in its own
    dtype (None: a plain sum). Returns what went to the collective."""
    sent = [leaves[i] for i in idxs]
    with jax.named_scope(_names.SCOPE_REDUCE):
        summed = lax.psum(sent, axis)
    if n is not None:
        with jax.named_scope(_names.SCOPE_UNPACK):
            summed = [(s / n).astype(s.dtype) for s in summed]
    for i, s in zip(idxs, summed):
        leaves[i] = s
    return sent


def _sync_flat_group(leaves, idxs, dtype, n, axis, wire_dtype):
    """Pack the leaves ``idxs`` of one dtype into a flat buffer, reduce it
    on the compressed-wire ring and cut it back into ``leaves``, each
    phase under its own scope (``n``: what to divide the sum by, None for
    a plain sum); returns the buffer that went to the collective. Only a
    wire format that quantizes flat buffers needs this."""
    with jax.named_scope(_names.SCOPE_PACK):
        flats = [jnp.reshape(leaves[i], (-1,)) for i in idxs]
        splits = np.cumsum([f.shape[0] for f in flats])[:-1]
        cat = jnp.concatenate(flats)
    with jax.named_scope(_names.SCOPE_REDUCE):
        buf = _prim.ring_allreduce(cat, axis, wire_dtype=wire_dtype)
    with jax.named_scope(_names.SCOPE_UNPACK):
        if n is not None:
            buf = (buf / n).astype(dtype)
        for part, i in zip(jnp.split(buf, splits), idxs):
            leaves[i] = jnp.reshape(part, leaves[i].shape)
    return [cat]


def in_graph_synchronize_gradients(
    grads, axis: str = "mpi", average: bool = True,
    wire_dtype: Optional[str] = None,
):
    """Reduce every leaf over the mesh axis: the compiled analog of
    synchronizeGradients and of registerAsyncMPIBackward's per-layer
    overlap, which here is XLA's to schedule. The leaves go to one
    ``lax.psum`` as they lie: no flat buffer, integer leaves stay exact
    and no leaf is promoted.

    ``wire_dtype`` ('bf16' | 'int8') replaces the psum with the
    compressed-wire ppermute ring (block-quantized send, f32 accumulate)
    for a dtype group the wire engages for: float32 leaves that together
    reach the tuned cutoff. Quantization works on a flat buffer, so such
    a group, and only such a group, is packed into one."""
    leaves, treedef = tree_util.tree_flatten(grads)
    n = lax.psum(1, axis) if average else None
    by_dtype: Dict = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.result_type(leaf), []).append(i)
    lie, reduced = [], []
    with jax.named_scope(_names.SCOPE_GRAD_SYNC):
        for dtype, idxs in by_dtype.items():
            nelem = sum(int(np.prod(leaves[i].shape)) for i in idxs)
            if _prim.wire_engages(wire_dtype, dtype, nelem):
                reduced += _sync_flat_group(
                    leaves, idxs, dtype, n, axis, wire_dtype)
            else:
                lie += idxs
        if lie:
            reduced += _sync_leaves(leaves, sorted(lie), n, axis)
    _note_sync(reduced)
    return tree_util.tree_unflatten(treedef, leaves)


def in_graph_synchronize_parameters(params, axis: str = "mpi", root: int = 0):
    idx = lax.axis_index(axis)
    return tree_util.tree_map(
        lambda w: lax.psum(jnp.where(idx == root, w, jnp.zeros_like(w)), axis),
        params,
    )


# ---------------------------------------------------------------------------
# replica-consistency invariant (init.lua:372-395)
# ---------------------------------------------------------------------------


def check_with_allreduce(
    params, comm: Optional[Communicator] = None, tol: float = 1e-7
) -> None:
    """Assert replicas are consistent: for each leaf, allreduced |mean| and
    |var| must equal size * local value to ``tol`` (``init.lua:387-394``).
    Cheap, and catches desync bugs early."""
    comm = _comm(comm)
    p = comm.size
    buf = _flatten_stacked(params, p).astype(jnp.float32)
    stats = jnp.stack(
        [jnp.abs(jnp.mean(buf, axis=1)), jnp.abs(jnp.var(buf, axis=1))], axis=1
    )

    def _rows(a):
        # multi-controller: fetching the global array would raise (rows
        # on remote processes are non-addressable); map global row index
        # -> row for whatever THIS process can see — each process checks
        # the invariant on its ranks' rows, together covering all p
        if getattr(a, "is_fully_addressable", True):
            arr = np.asarray(a)
            return {i: arr[i] for i in range(arr.shape[0])}
        out = {}
        for s in a.addressable_shards:
            start = s.index[0].start or 0
            d = np.asarray(s.data)
            for j in range(d.shape[0]):
                out[start + j] = d[j]
        return out

    red = _rows(collectives.allreduce_tensor(stats, comm=comm))
    loc = _rows(stats)
    common = sorted(set(red) & set(loc))
    reduced = np.stack([red[i] for i in common])
    local = np.stack([loc[i] for i in common])
    err = np.abs(reduced / p - local).max()
    if err > tol * max(1.0, np.abs(local).max()):
        raise AssertionError(
            f"replica desync detected: |allreduce/p - local| = {err:.3e} "
            f"(tol {tol})"
        )
