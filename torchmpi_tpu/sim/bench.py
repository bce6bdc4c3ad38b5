"""Coordinator-scalability curve: the control plane at 256..10k ranks.

A count of what the control plane does at a size no host here reaches,
on a virtual clock — not a speed. Per world size it
forms a fleet, runs a ~1% death wave through the REAL coordinator
(bulk formation, heartbeat sweep, barrier release with the aggregated
summary), prices the redistribution with the real reshard plan, and
re-forms PS replica chains with the real planner — reporting:

- ``resize_commit_s``      epoch publish -> redistribution commit
  (virtual seconds: the modeled-network cost of the real plan)
- ``barrier_reply_bytes`` / ``view_bytes``  per-member control-plane
  payloads (the curve that caught the O(epochs x world) view history)
- ``reform_*``             chain re-formation fan-out (copies per new
  head, total copied bytes) at ``ps_replication`` 3
- ``plan_id`` / ``plan_est_us``  the schedule compiler's pick for the
  fleet's allreduce at that scale
- ``wall_s``               REAL seconds the simulation took (the
  coordinator-bottleneck proxy: the state machine itself is what runs)
"""

from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Any, Dict, List

from .. import constants
from ..parameterserver.server import initial_chains, reform_layout
from .fleet import SimFleet, reform_copies

DEFAULT_WORLDS = (256, 1024, 4096, 10000)
#: replica-chain length the curve measures re-formation at; the CI
#: fan-out gate (<= 2x this) derives from the same constant
REPLICATION = 3


def bench_point(world: int, seed: int = 17,
                death_fraction: float = 0.01) -> Dict[str, Any]:
    # the watchdog override lives HERE, not only in bench_curve: the
    # determinism replay in check_curve calls bench_point directly and
    # must run under the same knobs as the original point
    prev_wd = constants.get("watchdog_timeout_seconds")
    constants.set("watchdog_timeout_seconds", 0)
    try:
        return _bench_point(world, seed, death_fraction)
    finally:
        constants.set("watchdog_timeout_seconds", prev_wd)


def _bench_point(world: int, seed: int,
                 death_fraction: float) -> Dict[str, Any]:
    t_wall = time.perf_counter()
    fleet = SimFleet(
        world, seed=seed, group_size=8, steps=6,
        state_elems=1 << 18,
    )
    n_dead = max(1, int(world * death_fraction))
    # a spread wave (not a contiguous block): adjacent deaths >= the
    # replication factor would wipe whole ring chains, which is a
    # checkpoint-restore event, not a failover measurement. t=0.7 lands
    # mid-run at every world size (the smallest fleet is still stepping)
    stride = max(1, world // n_dead)
    dead = [(i * stride + stride // 2) % world for i in range(n_dead)]
    fleet.kill(dead, t=0.7)
    stats = fleet.run(horizon_s=30.0)
    resizes = stats["resizes"]
    post_death = [r for r in resizes if r["world_old"] > r["world_new"]]
    commit = post_death[-1] if post_death else (
        resizes[-1] if resizes else {}
    )
    plan_id, plan_s = fleet._plan(world)
    # chain re-formation fan-out at replication 3 over the same wave,
    # through the REAL planners (initial_chains + reform_layout)
    owners = list(range(world))
    chains = initial_chains(owners, REPLICATION)
    live = [p for p in owners if p not in set(dead)]
    new_owners, new_chains = reform_layout(
        owners, chains, live, REPLICATION
    )
    acct = reform_copies(owners, chains, new_owners, new_chains)
    return {
        "world": world,
        "dead": n_dead,
        "resize_commit_s": commit.get("commit_s"),
        "publish_to_release_s": commit.get("publish_to_release_s"),
        "barrier_reply_bytes": commit.get("barrier_reply_bytes"),
        "view_bytes": commit.get("view_bytes"),
        "redistribution_wire_bytes": commit.get(
            "redistribution_wire_bytes"
        ),
        "resize_epochs": len(resizes),
        "reform_copies_total": acct["copies_total"],
        "reform_copies_changed": acct["copies_changed"],
        "reform_max_copies_per_head": acct["max_copies_per_head"],
        "plan_id": plan_id,
        "plan_est_us": round(plan_s * 1e6, 3),
        "events": stats["events"],
        "wall_s": round(time.perf_counter() - t_wall, 3),
    }


def bench_curve(worlds=DEFAULT_WORLDS, seed: int = 17
                ) -> List[Dict[str, Any]]:
    return [bench_point(int(w), seed=seed) for w in worlds]


def check_curve(points: List[Dict[str, Any]], seed: int = 17
                ) -> List[str]:
    """Gates over the curve (``tests/test_sim.py``); failures as
    strings (empty = pass)."""
    failures: List[str] = []
    by_world = {p["world"]: p for p in points}
    for p in points:
        if p["resize_commit_s"] is None:
            failures.append(f"world {p['world']}: death wave never "
                            "resized")
        if p["resize_epochs"] < 2:
            failures.append(
                f"world {p['world']}: expected formation + death "
                f"resize, got {p['resize_epochs']} epoch(s)"
            )
        if p["reform_max_copies_per_head"] > 2 * REPLICATION:
            failures.append(
                f"world {p['world']}: reform fan-out "
                f"{p['reform_max_copies_per_head']} copies on one head "
                "(> 2x replication) — re-formation hotspot"
            )
    worlds = sorted(by_world)
    if len(worlds) >= 2:
        lo, hi = by_world[worlds[0]], by_world[worlds[-1]]
        ratio_n = hi["world"] / lo["world"]
        for key in ("barrier_reply_bytes", "view_bytes"):
            if lo.get(key) and hi.get(key):
                growth = hi[key] / lo[key]
                # per-member control payloads must scale (sub)linearly
                # with the member list — quadratic growth here is the
                # resize-storm bankruptcy the summary refactor removed
                if growth > 1.5 * ratio_n:
                    failures.append(
                        f"{key} grew {growth:.1f}x over a {ratio_n:.1f}x "
                        "world (super-linear per-member control payload)"
                    )
    # determinism: the smallest point replayed with the same seed must
    # reproduce byte-identically
    if points:
        again = bench_point(points[0]["world"], seed=seed)
        a = {k: v for k, v in points[0].items() if k != "wall_s"}
        b = {k: v for k, v in again.items() if k != "wall_s"}
        if json.dumps(a, sort_keys=True) != json.dumps(b, sort_keys=True):
            failures.append(
                f"world {points[0]['world']}: replay with seed {seed} "
                "diverged — determinism broken"
            )
    return failures


def check_synth_pricing(worlds=(1024, 4096),
                        payload_elems: int = 1 << 20) -> List[str]:
    """Gate (``tests/test_sim.py``): the composition algebra's
    synthesized plans must be generated and sim-priced at fleet scale,
    and must WIN there — at every checked world (>= 1k ranks) the best
    synthesized candidate prices strictly cheaper under the calibrated
    alpha-beta model than the best legacy candidate on the same
    route_small=False pricing path ``SimFleet._plan`` uses (a flat ring
    at 4k ranks pays ~2*world inter-fabric alphas; recursive halving
    pays 2*log2(world)). The enumerator must also stay O(candidates),
    not O(world): the synthesized candidate count is identical across
    the worlds and capped, and every synthesized plan's step list stays
    O(log world). Failures as strings (empty = pass)."""
    from ..schedule import (
        MAX_SYNTH_CANDIDATES, candidate_plans, is_synthesized,
    )
    from ..schedule.topology import Topology

    failures: List[str] = []
    prior = bool(constants.get("use_plan_synthesis"))
    if not prior:
        constants.set("use_plan_synthesis", True)
    try:
        counts = []
        for world in worlds:
            g = 8  # the SimFleet default group size (fleet.py)
            sizes = tuple([g] * (world // g)) + (
                (world % g,) if world % g else ()
            )
            topo = Topology(
                platform="cpu", group_sizes=sizes,
                cartesian=len(set(sizes)) == 1 and len(sizes) > 1,
                nodes=max(1, len(sizes)), name="sim",
            )
            cands = candidate_plans(
                "allreduce", payload_elems, 4, topo, backend="ring",
                wire="int8", route_small=False,
            )
            synth = [
                c for c in cands
                if is_synthesized(c.plan.generator) and c.feasible
                and c.cost_us is not None
            ]
            legacy = [
                c for c in cands
                if not is_synthesized(c.plan.generator) and c.feasible
                and c.cost_us is not None
            ]
            # pipeline twins are depth VARIANTS of a base candidate, not
            # new enumerator output — the boundedness contract is on the
            # depth-1 set the algebra actually derived
            base = [c for c in synth if c.plan.pipeline == 1]
            counts.append(len(base))
            if not base:
                failures.append(
                    f"world {world}: no synthesized candidate was "
                    "generated and priced"
                )
                continue
            if len(base) > MAX_SYNTH_CANDIDATES:
                failures.append(
                    f"world {world}: {len(base)} synthesized candidates "
                    f"(> cap {MAX_SYNTH_CANDIDATES}) — enumerator "
                    "unbounded"
                )
            best_synth = min(synth, key=lambda c: c.cost_us)
            for c in base:
                # steps are AGGREGATED (one entry per phase, count =
                # hops), so a candidate's IR size must stay O(log world)
                # entries even when its schedule walks O(world) hops
                if len(c.plan.steps) > 16 * max(1, world.bit_length()):
                    failures.append(
                        f"world {world}: {c.plan.plan_id} carries "
                        f"{len(c.plan.steps)} step entries — plan IR "
                        "must stay O(log world)"
                    )
            if legacy:
                best_legacy = min(legacy, key=lambda c: c.cost_us)
                if best_synth.cost_us >= best_legacy.cost_us:
                    failures.append(
                        f"world {world}: best synthesized plan "
                        f"{best_synth.plan.plan_id} "
                        f"({best_synth.cost_us:.1f}us) does not beat the "
                        f"best legacy plan {best_legacy.plan.plan_id} "
                        f"({best_legacy.cost_us:.1f}us) at fleet scale"
                    )
        if len(set(counts)) > 1:
            failures.append(
                f"synthesized candidate count varied with world size "
                f"{dict(zip((int(w) for w in worlds), counts))} — "
                "generation must be O(candidates), not O(world)"
            )
    finally:
        if not prior:
            constants.set("use_plan_synthesis", False)
    return failures


#: bound on supervised death-wave recovery: the whole episode — evict
#: the wave, commit the shrink, settle back to clean — must fit in this
#: many journaled actions (an unbounded remediation loop is the failure
#: mode the gate exists for)
MAX_RECOVERY_ACTIONS = 4


def check_supervised_recovery(ranks: int = 1024) -> List[str]:
    """Gate (``tests/test_sim.py``): supervised death-wave
    recovery at ``ranks`` must CONVERGE — the supervisor evicts the
    wave, a shrink commits, training resumes, no rollback — within
    :data:`MAX_RECOVERY_ACTIONS` actions, and the journal must replay
    byte-identically per seed. Failures as strings (empty = pass)."""
    import tempfile

    from .faults import run_scenario

    failures: List[str] = []
    runs = []
    for tag in ("a", "b"):
        out = Path(tempfile.mkdtemp(prefix=f"tm-sim-recover-{tag}-"))
        try:
            runs.append(
                run_scenario("death_wave", out, ranks=ranks,
                             supervise=True)
            )
        finally:
            import shutil

            shutil.rmtree(out, ignore_errors=True)
    res, replay = runs
    if not res["ok"]:
        failures += [f"supervised death_wave@{ranks}: {f}"
                     for f in res["failures"]]
    journal = res["recovery"]["journal"]
    if len(journal) > MAX_RECOVERY_ACTIONS:
        failures.append(
            f"supervised death_wave@{ranks}: recovery took "
            f"{len(journal)} actions (> {MAX_RECOVERY_ACTIONS}) — "
            "remediation did not converge"
        )
    if res["recovery"]["rolled_back"]:
        failures.append(
            f"supervised death_wave@{ranks}: escalated to rollback — "
            "a single recoverable wave must stay on the evict rung"
        )
    if json.dumps(journal, sort_keys=True) != json.dumps(
        replay["recovery"]["journal"], sort_keys=True
    ):
        failures.append(
            f"supervised death_wave@{ranks}: journal replay diverged "
            "— recovery determinism broken"
        )
    return failures
