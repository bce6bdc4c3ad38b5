"""Public collectives surface.

Namespace layout mirrors the reference Lua API
(``torchmpi/init.lua:145-365``): default (selector-routed) sync collectives at
the top level, per-backend namespaces (``xla`` ≙ stock MPI/NCCL, ``ring`` ≙
custom p2p), and ``async_`` variants returning :class:`SyncHandle`s. Scalar
collectives cross *processes* (multi-controller JAX) and are identity in
single-controller mode, where every rank lives in one process.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from ..runtime.communicator import Communicator
from ..runtime.handles import SyncHandle
from . import eager, primitives
from .eager import free_collective_resources, precompile
from .fusion import FusionBuffer, get_fusion_buffer
from .selector import collective_availability, selector


def _current_comm(comm: Optional[Communicator]) -> Communicator:
    if comm is not None:
        return comm
    from .. import runtime_state

    return runtime_state.current_communicator()


def _dispatch(op, x, comm, mode, backend=None, **kw):
    comm = _current_comm(comm)
    if backend is None:
        # Selector decisions are invariant per (comm, op, mode): memoize on
        # the communicator to keep eager launch overhead minimal (the
        # reference's <50us async-launch budget).
        cache = getattr(comm, "_selector_cache", None)
        if cache is None:
            cache = comm._selector_cache = {}
        backend = cache.get((op, mode))
        if backend is None:
            platform = comm._devices[0].platform
            backend = selector.select(
                op, platform, multinode=comm.num_nodes() > 1,
                # the fused plan dispatches synchronously; the selector
                # table only distinguishes sync/async
                mode="sync" if mode == "fused" else mode,
            )
            cache[(op, mode)] = backend
        if backend in ("ring", "pallas"):
            # The selector decides xla-vs-custom-ring; which custom ring
            # implements it is the ring_implementation constant (read per
            # call — it is mutable until freeze). A pallas ring asked for
            # where it cannot run fails in the kernel call, loudly.
            from .. import constants

            impl = constants.get("ring_implementation")
            backend = "ring" if impl == "ppermute" else "pallas"
    if mode == "sync":
        return eager.run(op, x, comm, backend=backend, **kw)
    if mode == "fused":
        # x is a LIST of same-dtype [p, n_i] slabs; one compiled plan
        # packs and reduces them (see eager.run_fused)
        return eager.run_fused(op, x, comm, backend=backend, **kw)
    return eager.run_async(op, x, comm, backend=backend, **kw)


# --- selector-routed (default) namespace -----------------------------------
def broadcast_tensor(x, root=0, comm=None):
    return _dispatch("broadcast", x, comm, "sync", root=root)


def reduce_tensor(x, root=0, comm=None):
    return _dispatch("reduce", x, comm, "sync", root=root)


def allreduce_tensor(x, comm=None, wire_dtype=None):
    """Sum-allreduce. ``wire_dtype`` ('full' | 'bf16' | 'int8') overrides
    the wire format for the bandwidth path (None = constants default;
    engages only for f32 payloads above wire_quant_min_elements)."""
    return _dispatch("allreduce", x, comm, "sync", wire_dtype=wire_dtype)


def allgather_tensor(x, comm=None):
    return _dispatch("allgather", x, comm, "sync")


def sendreceive_tensor(x, src, dst, comm=None):
    return _dispatch("sendreceive", x, comm, "sync", src=src, dst=dst)


def reducescatter_tensor(x, comm=None, wire_dtype=None):
    """Reduce-scatter over the LAST dim (dual of ``allgather_tensor``'s
    concat-last-dim contract): rank r's output block is slice r of the
    elementwise sum. Beyond the reference's surface (it has no
    reduce-scatter collective; its ring used one internally,
    ``lib/detail/collectives.cpp:128-326``) — exposed because ZeRO-style
    sharded optimizers consume it directly. ``wire_dtype`` as in
    :func:`allreduce_tensor`."""
    return _dispatch("reducescatter", x, comm, "sync", wire_dtype=wire_dtype)


def alltoall_tensor(x, comm=None):
    """All-to-all: input [p, p, ...] where block [r, s] is rank r's payload
    for rank s; output block [r, j] is what rank j sent rank r. Beyond the
    reference's surface (its alltoall-shaped traffic was the PS shard
    fan-out, ``lib/parameterserver.cpp:309-353``) — exposed because expert
    parallelism dispatches through it (``parallel/ep.py``)."""
    return _dispatch("alltoall", x, comm, "sync")


def allgatherv_tensor(blocks, comm=None, backend: str = "xla"):
    """Variable-size allgather over ragged last-dim per-rank blocks
    (reference ``Allgatherv``, ``lib/collectives.cpp:245-290``)."""
    return eager.run_allgatherv(blocks, _current_comm(comm), backend=backend)


class _BackendNS:
    """``mpi.p2p.*`` / ``mpi.nccl.*`` style per-backend namespaces."""

    def __init__(self, backend: str, mode: str):
        self._backend = backend
        self._mode = mode

    def broadcast_tensor(self, x, root=0, comm=None):
        return _dispatch("broadcast", x, comm, self._mode, self._backend, root=root)

    def reduce_tensor(self, x, root=0, comm=None):
        return _dispatch("reduce", x, comm, self._mode, self._backend, root=root)

    def allreduce_tensor(self, x, comm=None, wire_dtype=None):
        return _dispatch(
            "allreduce", x, comm, self._mode, self._backend,
            wire_dtype=wire_dtype,
        )

    def allgather_tensor(self, x, comm=None):
        return _dispatch("allgather", x, comm, self._mode, self._backend)

    def sendreceive_tensor(self, x, src, dst, comm=None):
        return _dispatch(
            "sendreceive", x, comm, self._mode, self._backend, src=src, dst=dst
        )

    def reducescatter_tensor(self, x, comm=None, wire_dtype=None):
        return _dispatch(
            "reducescatter", x, comm, self._mode, self._backend,
            wire_dtype=wire_dtype,
        )

    def alltoall_tensor(self, x, comm=None):
        return _dispatch("alltoall", x, comm, self._mode, self._backend)


class _AsyncNS(_BackendNS):
    def __init__(self, backend=None):
        super().__init__(backend, "async")
        self.xla = _BackendNS("xla", "async")
        self.ring = _BackendNS("ring", "async")
        self.pallas = _BackendNS("pallas", "async")


xla = _BackendNS("xla", "sync")
ring = _BackendNS("ring", "sync")
pallas = _BackendNS("pallas", "sync")
async_ = _AsyncNS()


# --- scalar collectives (init.lua:125-134) ---------------------------------
def broadcast_scalar(value, root: int = 0):
    """Broadcast a host scalar across *processes* (multi-controller)."""
    if jax.process_count() == 1:
        return value
    from jax.experimental import multihost_utils

    import numpy as np

    arr = multihost_utils.broadcast_one_to_all(
        np.asarray(value), is_source=jax.process_index() == root
    )
    return type(value)(arr)


def allreduce_scalar(value):
    if jax.process_count() == 1:
        return value
    from jax.experimental import multihost_utils

    import numpy as np

    # process_allgather then sum: every process contributes its scalar.
    gathered = multihost_utils.process_allgather(np.asarray(value))
    return type(value)(gathered.sum())


def reduce_scalar(value, root: int = 0):
    """Reduce (sum) a host scalar to process ``root``; every other process
    returns its input unchanged — the per-C-type ``C.torchmpi_reduce_*``
    surface of the reference (``torchmpi/init.lua:125-134``)."""
    if jax.process_count() == 1:
        return value
    from jax.experimental import multihost_utils

    import numpy as np

    gathered = multihost_utils.process_allgather(np.asarray(value))
    if jax.process_index() == root:
        return type(value)(gathered.sum())
    return value


def sendreceive_scalar(value, src: int, dst: int):
    """Point-to-point host scalar: process ``dst`` returns ``src``'s value,
    every other process (including ``src``) returns its input unchanged —
    ``C.torchmpi_sendreceive_*`` (``torchmpi/init.lua:125-134``). Collective
    over processes: all must call it (the transport is a broadcast-from-src
    with only ``dst`` adopting the result)."""
    if jax.process_count() == 1 or src == dst:
        return value
    from jax.experimental import multihost_utils

    import numpy as np

    arr = multihost_utils.broadcast_one_to_all(
        np.asarray(value), is_source=jax.process_index() == src
    )
    if jax.process_index() == dst:
        return type(value)(arr)
    return value


def barrier(comm=None):
    eager.barrier(_current_comm(comm))


def wait(handle):
    from ..runtime.handles import wait as _wait

    return _wait(handle)


__all__ = [
    "broadcast_tensor",
    "reduce_tensor",
    "allreduce_tensor",
    "allgather_tensor",
    "allgatherv_tensor",
    "sendreceive_tensor",
    "reducescatter_tensor",
    "alltoall_tensor",
    "broadcast_scalar",
    "allreduce_scalar",
    "reduce_scalar",
    "sendreceive_scalar",
    "barrier",
    "wait",
    "free_collective_resources",
    "precompile",
    "FusionBuffer",
    "get_fusion_buffer",
    "xla",
    "ring",
    "pallas",
    "async_",
    "selector",
    "collective_availability",
    "eager",
    "primitives",
]
