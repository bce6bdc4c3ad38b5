"""torchmpi_tpu — a TPU-native distributed training framework.

A brand-new framework with the capabilities of facebookresearch/TorchMPI,
re-designed for TPU: hierarchical named communicators over JAX device meshes
(ICI × DCN instead of MPI_COMM_WORLD splits and cudaIPC groups), a full
sync/async collectives surface with XLA-builtin and custom ring backends plus
a runtime selector, NN-level data-parallel helpers, an AllReduceSGD training
engine, and a host-side sharded parameter server (Downpour / EASGD / DSGD).

Public API shape follows the reference (``torchmpi/init.lua``):

    import torchmpi_tpu as mpi
    mpi.start()
    y = mpi.allreduce_tensor(x)           # selector-routed
    y = mpi.ring.allreduce_tensor(x)      # explicit custom-ring backend
    h = mpi.async_.allreduce_tensor(x)    # async -> SyncHandle
    mpi.wait(h)
    mpi.stop()
"""

from . import constants, telemetry
from .collectives import (
    allgather_tensor,
    allgatherv_tensor,
    allreduce_scalar,
    allreduce_tensor,
    async_,
    barrier,
    broadcast_scalar,
    broadcast_tensor,
    collective_availability,
    free_collective_resources,
    alltoall_tensor,
    pallas,
    reduce_scalar,
    reduce_tensor,
    reducescatter_tensor,
    ring,
    selector as collective_selector,
    sendreceive_scalar,
    sendreceive_tensor,
    wait,
    xla,
)
from .runtime.communicator import Communicator, split_by_keys
from .runtime.handles import SyncHandle, sync_all
from .runtime_state import (
    communicator_names,
    describe,
    current_communicator,
    num_nodes_in_communicator,
    num_processes,
    push_communicator,
    rank,
    set_collective_span,
    set_communicator,
    size,
    stack,
    start,
    started,
    stop,
)

# Submodules as attributes, matching the reference's surface (torchmpi.nn,
# torchmpi.parameterserver, ...): `import torchmpi_tpu as mpi; mpi.nn.*`
# must work without a separate import. Imported LAST — each pulls from
# `collectives`/`runtime_state` above, so the order avoids cycles.
from . import data, engine, nn, parallel, parameterserver, utils  # noqa: E402

__version__ = "0.5.0"

__all__ = [
    "start",
    "stop",
    "started",
    "rank",
    "size",
    "num_processes",
    "barrier",
    "push_communicator",
    "set_communicator",
    "set_collective_span",
    "communicator_names",
    "describe",
    "num_nodes_in_communicator",
    "current_communicator",
    "stack",
    "Communicator",
    "split_by_keys",
    "SyncHandle",
    "sync_all",
    "wait",
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
    "xla",
    "ring",
    "pallas",
    "async_",
    "collective_selector",
    "collective_availability",
    "free_collective_resources",
    "constants",
    "telemetry",
    "__version__",
]
