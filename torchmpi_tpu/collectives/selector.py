"""Collective backend selector.

Analog of ``mpi.collectiveSelector`` (``torchmpi/init.lua:463-555``): a
preference table keyed on ``(platform, single/multi node, sync/async,
collective)`` listing backend implementations in preference order; the first
*available* one wins. The reference's axes were
``[cpu|gpu][singlenode|multinode][sync|async]`` with backends
``{p2p, nccl, gloo, mpi}``; here the platforms are ``cpu|tpu`` and the
backends are:

- ``xla``  — fused XLA collective (the vendor path; NCCL/MPI analog)
- ``ring`` — custom chunked ``ppermute`` ring (the custom-p2p analog)
- ``pallas`` — Pallas ICI-RDMA ring kernels (TPU only; the cudaIPC analog)

``collective_availability()`` renders the availability matrix string like the
reference's introspection dump (``init.lua:557-660``).

The selector answers *which backend executor is available/preferred*;
*which schedule* a request actually runs (flat / hierarchical / staged
/ tree, cost-modeled and cached) is the schedule compiler's decision —
``python -m torchmpi_tpu.schedule --explain`` is the introspection
surface for that, superseding this module's static preference dump for
routing questions.
"""

from __future__ import annotations

from typing import Dict, List

import jax

_COLLECTIVES = (
    "broadcast",
    "reduce",
    "allreduce",
    "sendreceive",
    "allgather",
    "reducescatter",
    "alltoall",
)


def _pallas_available() -> bool:
    from ..ops import ring_kernels

    # the interpret test hook makes pallas runnable anywhere: let the
    # selector/autotuner see it too, so interpret-mode coverage is
    # end-to-end (dispatch included), not just direct kernel calls
    return ring_kernels._FORCE_INTERPRET or ring_kernels.available()


def backend_availability() -> Dict[str, bool]:
    return {
        "xla": True,
        "ring": True,
        "pallas": _pallas_available(),
    }


# single-home re-exports (primitives owns the encodings, eager owns the
# op set — re-deriving them here would let the dump drift from dispatch)
from .eager import _WIRE_OPS as WIRE_COLLECTIVES  # noqa: E402
from .primitives import WIRE_DTYPES as WIRE_FORMATS  # noqa: E402


def wire_format_availability() -> Dict[str, bool]:
    """Which wire encodings the custom-ring backends can put on the wire
    (every encoding is implemented on both the ppermute and pallas rings,
    so availability tracks the backends, not the formats)."""
    avail = backend_availability()
    custom = avail["ring"] or avail["pallas"]
    return {"full": True, "bf16": custom, "int8": custom}


# Preference order per (platform, nodes, mode, collective).
# Mirrors the reference's choices in spirit: single-node sync allreduce
# prefers the custom ring (its cudaIPC ring beat NCCL, README.md:104-106);
# small sizes are rerouted to 'xla' by eager.op_route either way.
_DEFAULT: Dict[str, Dict[str, Dict[str, Dict[str, List[str]]]]] = {
    "cpu": {
        "singlenode": {
            "sync": {c: ["xla", "ring"] for c in _COLLECTIVES},
            "async": {c: ["xla", "ring"] for c in _COLLECTIVES},
        },
        "multinode": {
            "sync": {c: ["xla", "ring"] for c in _COLLECTIVES},
            "async": {c: ["xla", "ring"] for c in _COLLECTIVES},
        },
    },
    "tpu": {
        "singlenode": {
            "sync": {
                "broadcast": ["pallas", "ring", "xla"],
                "reduce": ["ring", "xla"],
                "allreduce": ["pallas", "ring", "xla"],
                "sendreceive": ["xla", "ring"],
                "allgather": ["xla", "ring"],
                "reducescatter": ["xla", "ring"],
                "alltoall": ["xla", "ring"],
            },
            "async": {c: ["xla", "ring"] for c in _COLLECTIVES},
        },
        "multinode": {
            # Cross-host (DCN) traffic: trust XLA's hierarchical lowering
            # first, custom ring second (the staged/direct choice is a
            # constant, like kUseStagedCollectives).
            "sync": {c: ["xla", "ring"] for c in _COLLECTIVES},
            "async": {c: ["xla", "ring"] for c in _COLLECTIVES},
        },
    },
}


class CollectiveSelector:
    def __init__(self):
        self.table = _DEFAULT

    def select(
        self,
        collective: str,
        platform: str = None,
        multinode: bool = False,
        mode: str = "sync",
    ) -> str:
        platform = platform or jax.devices()[0].platform
        if platform not in self.table:
            raise ValueError(
                f"no collective preference table for platform {platform!r} "
                f"(supported: {sorted(self.table)})"
            )
        nodes = "multinode" if multinode else "singlenode"
        prefs = self.table[platform][nodes][mode][collective]
        avail = backend_availability()
        for b in prefs:
            if avail.get(b):
                return b
        return "xla"

    def select_wire(self, collective: str, nelem: int = None,
                    dtype=None) -> str:
        """The wire format an eager call of ``collective`` would ship:
        the ``wire_dtype`` constant (the autotuner's persisted pick)
        gated by the engagement rules. ``nelem``/``dtype`` None = assume
        a large f32 payload (the routing question, not a specific call).
        """
        import jax.numpy as jnp

        from .. import constants
        from .eager import resolve_wire_dtype

        if nelem is None:
            nelem = constants.get("wire_quant_min_elements")
        return resolve_wire_dtype(
            collective, nelem, dtype if dtype is not None else jnp.float32
        )

    def describe(self) -> str:
        from .. import constants

        avail = backend_availability()
        lines = ["Backend availability: " + ", ".join(
            f"{k}={'yes' if v else 'no'}" for k, v in avail.items()
        )]
        wf = wire_format_availability()
        lines.append(
            "Wire formats (fp32 "
            + "/".join(WIRE_COLLECTIVES)
            + " >= wire_quant_min_elements): "
            + ", ".join(f"{k}={'yes' if v else 'no'}" for k, v in wf.items())
            + f" -> default {constants.get('wire_dtype')}"
        )
        for coll in WIRE_COLLECTIVES:
            # what a large f32 payload of this collective would ship
            lines.append(f"wire.{coll}: -> {self.select_wire(coll)}")
        for platform, nodes_tbl in self.table.items():
            for nodes, mode_tbl in nodes_tbl.items():
                for mode, coll_tbl in mode_tbl.items():
                    for coll, prefs in coll_tbl.items():
                        chosen = self.select(coll, platform, nodes == "multinode", mode)
                        lines.append(
                            f"{platform}.{nodes}.{mode}.{coll}: "
                            f"{' > '.join(prefs)} -> {chosen}"
                        )
        return "\n".join(lines)


selector = CollectiveSelector()


def collective_availability() -> str:
    return selector.describe()
