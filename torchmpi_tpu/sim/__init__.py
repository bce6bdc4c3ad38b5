"""simfleet: a deterministic 1k-10k-rank fault simulator that drives
the REAL control plane.

One host drives four chips and a handful of real processes; the
north star is production scale. This package turns scale from a
hardware-access problem into a test suite (ROADMAP item 5, the modeled-
fleet tradition of Awan et al.'s characterization and GC3's plan
evaluation over declared networks — PAPERS.md): a discrete-event
simulation with a seeded virtual clock runs the **real** control-plane
code over thousands of simulated ranks on a **modeled** network:

==========================  ==============================================
real (the deployed code)    modeled (priced, not executed)
==========================  ==============================================
ElasticCoordinator           data-plane transfer *times* (the reshard
  membership/epoch state     plan's bytes priced by the ``plan_cost_*``
  machine, resize barrier    alpha-beta model)
  + release summary
plan_transfers (reshard      per-link latencies (ICI/DCN/host alpha-beta
  source/dest schedule)      constants, seeded jitter)
schedule compiler            step *compute* time (``sim_step_seconds``)
  candidate generation +
  cost model (plan_id in
  every telemetry entry)
PS chain derivation +        server apply *rate* (host-link cost of the
  re-formation planner       payload)
  (initial_chains /
  reform_layout)
admission control            socket I/O (latency drawn per frame)
  (admission_decision) +
  BUSY backoff
  (busy_backoff_s)
telemetry formats +          watchdog/heartbeat *timing* (virtual clock)
  the PR 6 analyzer
  (verdicts on sim dumps)
==========================  ==============================================

Two runs with the same seed are byte-identical (``analysis.json``
included); a different seed changes event timing but never the
analyzer's verdict. Fault scenarios (:mod:`.faults`) are JSON files —
rank-death waves, stragglers, partitions, BUSY storms, torn resizes —
each naming the verdict ``telemetry.analyze`` must reach, asserted in
CI (``scripts/ci.sh`` sim-smoke; the coordinator curve of
:mod:`.bench` is gated in ``tests/test_sim.py``).
"""

import importlib

from .clock import derive_seed, rng_for, wait_until  # noqa: F401
from .core import EventLoop  # noqa: F401

# lazily resolved: fleet/faults pull the schedule compiler, the PS
# planners and telemetry.analyze — the multiprocess-test workers import
# this package only for the light seed/wait helpers above and must not
# pay the control-plane import at every subprocess start
_LAZY = {
    "ModeledNetwork": ".net",
    "SimActuator": ".fleet",
    "SimFleet": ".fleet",
    "SimPS": ".fleet",
    "WALL_BASE": ".fleet",
    "SCENARIO_DIR": ".faults",
    "check_recovery": ".faults",
    "load_scenario": ".faults",
    "run_scenario": ".faults",
    "verdict_of": ".faults",
}

__all__ = [
    "EventLoop", "ModeledNetwork", "SimActuator", "SimFleet", "SimPS",
    "WALL_BASE", "derive_seed", "rng_for", "wait_until",
    "SCENARIO_DIR", "check_recovery", "load_scenario", "run_scenario",
    "verdict_of",
]


def __getattr__(name: str):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(importlib.import_module(mod, __name__), name)
