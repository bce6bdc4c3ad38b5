"""simfleet: the deterministic fault simulator driving the real control
plane (torchmpi_tpu.sim).

What these tests pin down:

- the event loop and seeded RNG streams are deterministic;
- every packaged fault scenario reaches the verdict named in its file
  through the REAL ``telemetry.analyze`` over format-identical dumps;
- replaying a scenario with the same seed is byte-identical
  (``analysis.json`` included); changing the seed changes event timing
  but never the verdict;
- the coordinator's barrier-release summary and view payloads scale
  linearly with the member list (the resize-storm regression gate);
- the real chain re-formation planner bounds per-head fan-out;
- a commit layout older than the coordinator's history window fails
  LOUDLY (src_unresolved -> DataLoss) instead of silently
  redistributing from the wrong member list.
"""

from __future__ import annotations

import json

import pytest

from torchmpi_tpu import constants
from torchmpi_tpu.sim import (
    EventLoop,
    SimFleet,
    derive_seed,
    rng_for,
    run_scenario,
)
from torchmpi_tpu.sim.bench import bench_point

pytestmark = pytest.mark.filterwarnings("ignore")


# ---------------------------------------------------------------------------
# core determinism
# ---------------------------------------------------------------------------


def test_event_loop_orders_by_time_then_schedule_order():
    loop = EventLoop()
    out = []
    loop.at(2.0, out.append, "c")
    loop.at(1.0, out.append, "a")
    loop.at(1.0, out.append, "b")  # same instant: scheduling order
    loop.after(0.5, out.append, "z")
    end = loop.run()
    assert out == ["z", "a", "b", "c"]
    assert end == 2.0
    # the past is immutable: scheduling before now clamps to now
    loop.at(0.0, out.append, "late")
    loop.run()
    assert out[-1] == "late" and loop.now == 2.0


def test_seeded_rng_streams_are_stable_and_independent():
    assert derive_seed("x", 1) == derive_seed("x", 1)
    assert derive_seed("x", 1) != derive_seed("x", 2)
    a1 = [rng_for(7, "net").random() for _ in range(5)]
    a2 = [rng_for(7, "net").random() for _ in range(5)]
    b = [rng_for(7, "ps").random() for _ in range(5)]
    assert a1 == a2 and a1 != b


def test_clean_fleet_reaches_clean_verdict(tmp_path):
    res = run_scenario(
        {"name": "clean", "ranks": 16, "steps": 4, "seed": 3,
         "constants": {"watchdog_timeout_seconds": 0},
         "expected": {"verdict": "clean", "steps_completed_min": 4}},
        tmp_path,
    )
    assert res["ok"], res["failures"]
    rz = res["report"]["resize"]
    assert rz["status"] == "ok"  # formation barrier: every rank entered
    assert res["report"]["desync"]["status"] == "none"


# ---------------------------------------------------------------------------
# the packaged scenarios: each must reach its named verdict
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "name,ranks",
    [
        ("death_wave", 64),
        ("straggler", None),
        ("partition", None),
        ("torn_resize", None),
        ("busy_storm", None),
        ("read_storm", 64),
    ],
)
def test_packaged_scenario_reaches_named_verdict(tmp_path, name, ranks):
    res = run_scenario(name, tmp_path, ranks=ranks)
    assert res["ok"], (name, res["verdict"], res["failures"])


def test_death_wave_diagnosis_names_the_dead(tmp_path):
    res = run_scenario("death_wave", tmp_path, ranks=64)
    assert res["verdict"] == "hang"
    never = set()
    for h in res["report"]["hangs"]:
        for d in h["stuck_collectives"]:
            never.update(d["ranks_never_entered"])
    assert {17, 18, 19, 20} <= never
    # and the resize itself was clean: every SURVIVOR entered
    assert res["report"]["resize"]["status"] == "ok"


def test_partition_surfaces_dead_marks_in_ps_health(tmp_path):
    res = run_scenario("partition", tmp_path)
    servers = res["report"]["ps"]["servers"]
    marks = [
        s["connections"] for s in servers.values()
        if s.get("connections")
        and "dead_marks_active" in s["connections"]
    ]
    assert marks, "no rank surfaced failover dead-marks"
    assert sum(
        c.get("dead_mark_expiries", 0) for c in marks
    ) >= 1  # the bounded split-brain window closed observably


# ---------------------------------------------------------------------------
# replay determinism
# ---------------------------------------------------------------------------


def test_same_seed_replay_is_byte_identical(tmp_path):
    a = run_scenario("torn_resize", tmp_path / "a")
    b = run_scenario("torn_resize", tmp_path / "b")
    assert (tmp_path / "a" / "analysis.json").read_bytes() == (
        tmp_path / "b" / "analysis.json"
    ).read_bytes()
    assert a["stats"] == b["stats"]
    # every per-rank dump replays byte-identically too
    for p in sorted((tmp_path / "a").glob("telemetry_rank_*.json")):
        assert p.read_bytes() == (
            tmp_path / "b" / p.name
        ).read_bytes(), p.name


def test_seed_change_moves_events_but_not_the_verdict(tmp_path):
    base = run_scenario("death_wave", tmp_path / "a", ranks=64)
    other = run_scenario(
        "death_wave", tmp_path / "b", ranks=64, seed=4242
    )
    assert base["verdict"] == other["verdict"] == "hang"
    assert other["ok"], other["failures"]
    assert (tmp_path / "a" / "analysis.json").read_bytes() != (
        tmp_path / "b" / "analysis.json"
    ).read_bytes()  # timing moved: the dumps differ, the verdict holds


# ---------------------------------------------------------------------------
# coordinator scale behavior (the bench gates, at test-sized worlds)
# ---------------------------------------------------------------------------


def test_control_payloads_scale_linearly_with_world():
    lo = bench_point(64, seed=5)
    hi = bench_point(256, seed=5)
    ratio = 256 / 64
    for key in ("barrier_reply_bytes", "view_bytes"):
        growth = hi[key] / lo[key]
        assert growth <= 1.5 * ratio, (
            f"{key} grew {growth:.1f}x over a {ratio:.0f}x world — "
            "super-linear per-member control payload "
            "(resize-storm regression)"
        )
    from torchmpi_tpu.sim.bench import REPLICATION
    assert hi["reform_max_copies_per_head"] <= 2 * REPLICATION


def test_bulk_join_equals_serial_joins_in_one_epoch():
    from torchmpi_tpu.reshard.elastic import ElasticCoordinator

    loop = EventLoop()
    bulk = ElasticCoordinator(serve=False, clock=loop.time)
    mids = bulk.bulk_join([("h", 1), ("h", 2), ("h", 3)])
    assert mids == [0, 1, 2]
    assert bulk.epoch == 1  # ONE membership change for the cohort
    assert bulk.members() == [0, 1, 2]
    serial = ElasticCoordinator(serve=False, clock=loop.time)
    for port in (1, 2, 3):
        serial._handle({"op": "join", "host": "h", "data_port": port})
    assert serial.members() == bulk.members()
    assert serial.epoch == 3  # the cost bulk_join amortizes away


def test_barrier_release_summary_carries_the_agreement():
    from torchmpi_tpu.reshard.elastic import ElasticCoordinator

    loop = EventLoop()
    coord = ElasticCoordinator(serve=False, clock=loop.time)
    coord.bulk_join([("h", p) for p in range(3)])
    committed = coord.epoch  # the epoch the survivors are laid out per
    coord._handle({"op": "leave", "mid": 2})  # a death: epoch bumps
    epoch = coord.epoch
    vals = {
        0: {"step": 5, "stateful": True, "was": committed},
        1: {"step": 6, "stateful": True, "was": committed},
    }
    assert coord.barrier_arrive(0, epoch, vals[0]) is None
    assert coord.barrier_poll(epoch) is None
    rep = coord.barrier_arrive(1, epoch, vals[1])
    assert rep["ok"]
    s = rep["summary"]
    assert s["stateful"] == [0, 1]
    assert s["anchor"] == 1 and s["step"] == 6  # max step wins
    assert s["was"] == [committed]
    assert s["src_members"] == [0, 1, 2]  # the committed epoch's world
    # every later poll returns the SAME release object
    assert coord.barrier_poll(epoch) is rep


def test_commit_older_than_history_window_is_loud():
    """A resize storm can outlast the coordinator's bounded member-list
    history. The release summary must say so (src_unresolved) — the
    member turns that into DataLoss — rather than silently naming the
    wrong source layout (the pre-simfleet behavior)."""
    from torchmpi_tpu.reshard import elastic as E

    loop = EventLoop()
    coord = E.ElasticCoordinator(serve=False, clock=loop.time)
    coord.bulk_join([("h", p) for p in range(2)])
    # storm: bump far past the history window
    with coord._cv:
        for _ in range(E._HISTORY_EPOCHS + 4):
            coord._bump_epoch_locked()
    epoch = coord.epoch
    val = {"step": 9, "stateful": True, "was": 1}  # committed long ago
    coord.barrier_arrive(0, epoch, val)
    rep = coord.barrier_arrive(1, epoch, val)
    assert rep["ok"] and rep["summary"].get("src_unresolved")
    # ... and a last-committed epoch still inside the window resolves
    with coord._cv:
        coord._bump_epoch_locked()
    epoch = coord.epoch
    val = {"step": 9, "stateful": True, "was": epoch - 1}
    coord.barrier_arrive(0, epoch, val)
    rep = coord.barrier_arrive(1, epoch, val)
    assert rep["ok"] and not rep["summary"].get("src_unresolved")
    assert rep["summary"]["src_members"] == [0, 1]


def test_reform_layout_fanout_bounded_on_spread_wave():
    from torchmpi_tpu.parameterserver.server import (
        initial_chains,
        reform_layout,
    )

    world, rep = 128, 3
    owners = list(range(world))
    chains = initial_chains(owners, rep)
    dead = {10, 40, 70, 100}
    live = [p for p in owners if p not in dead]
    new_owners, new_chains = reform_layout(owners, chains, live, rep)
    assert all(p not in dead for c in new_chains for p in c)
    assert all(len(c) == rep for c in new_chains)
    per_head = {}
    for r, c in enumerate(new_chains):
        if new_owners[r] != owners[r] or c != chains[r]:
            per_head[new_owners[r]] = per_head.get(new_owners[r], 0) \
                + len(c) - 1
    assert per_head and max(per_head.values()) <= 2 * rep


def test_fleet_runs_real_plan_ids_per_world_size(tmp_path):
    res = run_scenario(
        {"name": "plan-id", "ranks": 24, "steps": 8, "seed": 2,
         "group_size": 8,
         "constants": {"watchdog_timeout_seconds": 0},
         "events": [{"kind": "die", "t": 0.7, "align": "gap",
                     "ranks": [5]}]},
        tmp_path,
    )
    plans = set()
    for p in sorted(tmp_path.glob("telemetry_rank_0.json")):
        snap = json.loads(p.read_text())
        for e in snap["flight_recorder"]["entries"]:
            if e["comm"].startswith("global["):
                plans.add((e["comm"], e["plan"]))
    worlds = {c for c, _ in plans}
    assert {"global[24]", "global[23]"} <= worlds
    # a fresh plan per world size, and plan ids present in every entry
    assert all(pid for _, pid in plans)
    assert len({pid for _, pid in plans}) == len(worlds)


def test_scenario_constants_are_restored(tmp_path):
    prev = constants.get("ps_pending_frame_budget")
    run_scenario("busy_storm", tmp_path)
    assert constants.get("ps_pending_frame_budget") == prev


# ---------------------------------------------------------------------------
# supervised recovery: the same scenarios with the RecoverySupervisor
# closing the loop (expected.recovery asserted per scenario file)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "name,ranks",
    [
        ("death_wave", 64),
        ("straggler", None),
        ("partition", None),
        ("torn_resize", None),
        ("busy_storm", None),
        ("read_storm", 64),
    ],
)
def test_supervised_scenario_meets_recovery_contract(tmp_path, name,
                                                     ranks):
    """Every packaged scenario carries an expected.recovery block: the
    verdict-driven ladder must land the named actions (and ONLY those),
    within the action bound, never before the hysteresis window —
    including busy_storm (a persistent ps-overload takes NO destructive
    action) and straggler (quarantine fires only after the verdict
    persisted N windows, never on a single noisy one)."""
    res = run_scenario(name, tmp_path, ranks=ranks, supervise=True)
    assert res["ok"], (name, res["failures"])
    hyst = constants.get("supervisor_hysteresis_windows")
    assert all(e["windows"] >= hyst for e in res["recovery"]["journal"])


def test_supervised_death_wave_shrinks_and_resumes(tmp_path):
    """The acceptance ladder in one scenario: hang/rank-dead -> evict
    the wave (one action, one epoch) -> committed shrink -> training
    resumed — no rollback, journal byte-identical per seed."""
    res = run_scenario("death_wave", tmp_path / "a", ranks=64,
                       supervise=True)
    assert res["ok"], res["failures"]
    journal = res["recovery"]["journal"]
    evicts = [e for e in journal if e["action"] == "evict-shrink"]
    assert evicts and evicts[0]["ranks"] == [17, 18, 19, 20]
    assert not res["recovery"]["rolled_back"]
    shrinks = [r for r in res["stats"]["resizes"]
               if r["world_old"] > r["world_new"]]
    assert len(shrinks) == 1  # the wave is ONE membership change
    assert res["stats"]["steps_completed"] >= 14  # training resumed
    # byte-identical replay per seed
    res2 = run_scenario("death_wave", tmp_path / "b", ranks=64,
                        supervise=True)
    assert json.dumps(journal, sort_keys=True) == json.dumps(
        res2["recovery"]["journal"], sort_keys=True
    )


def test_supervised_torn_resize_ends_in_rollback_decision(tmp_path):
    res = run_scenario("torn_resize", tmp_path, supervise=True)
    assert res["ok"], res["failures"]
    assert res["recovery"]["rolled_back"]
    last = res["recovery"]["journal"][-1]
    assert last["action"] == "rollback" and last["result"] == "applied"
    assert res["stats"]["rollback"]["reason"] == "resize-torn"


def test_supervised_seed_change_keeps_the_ladder_shape(tmp_path):
    base = run_scenario("death_wave", tmp_path / "a", ranks=64,
                        supervise=True)
    other = run_scenario("death_wave", tmp_path / "b", ranks=64,
                         seed=4242, supervise=True)
    assert base["ok"] and other["ok"], (base["failures"],
                                        other["failures"])
    assert (
        [e["action"] for e in base["recovery"]["journal"]]
        == [e["action"] for e in other["recovery"]["journal"]]
    )


def test_supervised_dry_run_decides_but_never_acts(tmp_path):
    """supervise_dry_run: the decisions are journaled (result
    'dry-run') but nobody is evicted — the fleet keeps limping, the
    dead ranks stay in the membership's hands (heartbeat sweep only)."""
    scn = dict(
        __import__("torchmpi_tpu.sim.faults", fromlist=["load_scenario"])
        .load_scenario("death_wave")
    )
    scn["ranks"] = 64
    scn["supervise_dry_run"] = True
    scn["expected"] = {"recovery": {}}  # decisions only, no contract
    res = run_scenario(scn, tmp_path, supervise=True)
    journal = res["recovery"]["journal"]
    assert journal and all(e["result"] == "dry-run" for e in journal)
    assert not res["stats"].get("rollback")


@pytest.mark.parametrize("gate", [
    "supervised_recovery", "curve", "synth_pricing"])
def test_sim_bench_gate_passes(gate):
    """The simulator's own lists of failures, at test-sized worlds:
    bounded supervised recovery with a journal that replays; every world
    of the curve resizes, its control payloads grow no faster than the
    member list, the smallest point replays; synthesized plans generated
    in O(candidates) and priced under every legacy family from 1k ranks."""
    from torchmpi_tpu.sim import bench

    failures = {
        "supervised_recovery":
            lambda: bench.check_supervised_recovery(ranks=128),
        "curve": lambda: bench.check_curve(bench.bench_curve((64, 256))),
        "synth_pricing": bench.check_synth_pricing,
    }[gate]()
    assert failures == []
