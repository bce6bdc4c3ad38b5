"""Recovery supervisor (torchmpi_tpu.supervise): policy, hysteresis,
bounded backoff, the escalation ladder, quarantine, the checkpoint
registry, and the live-plane surfaces (/actions, tm_supervisor_*).

Everything here is synchronous and clock-injected — the same
determinism contract the fleet simulator relies on."""

from __future__ import annotations

import json
import urllib.request

import pytest

from torchmpi_tpu import constants
from torchmpi_tpu.supervise import (
    A_EVICT,
    A_GROW,
    A_QUARANTINE,
    A_ROLLBACK,
    RecoverySupervisor,
    checkpoints,
    default_policy,
)


class Recorder:
    """An actuator that records calls; per-action success is settable."""

    def __init__(self, ok=True):
        self.calls = []
        self.ok = ok

    def evict(self, ranks, reason):
        self.calls.append(("evict", list(ranks), reason))
        return self.ok

    def grow(self, reason):
        self.calls.append(("grow", [], reason))
        return self.ok

    def rollback(self, reason):
        self.calls.append(("rollback", [], reason))
        return self.ok


def doc(verdict, ranks=(0, 1, 2, 3), dead=(), stuck=(),
        stragglers=None, resize=None):
    return {
        "verdict": verdict,
        "ranks": list(ranks),
        "dead_ranks": list(dead),
        "stuck": list(stuck),
        "stragglers": stragglers or {},
        "resize": resize or {},
    }


def mk(actuator=None, **kw):
    kw.setdefault("clock", lambda: 0.0)
    return RecoverySupervisor(actuator or Recorder(), **kw)


# ---------------------------------------------------------------------------
# hysteresis
# ---------------------------------------------------------------------------


def test_no_action_on_a_single_noisy_window():
    act = Recorder()
    sup = mk(act)
    assert sup.observe(doc("rank-dead", dead=[2]), now=0.0) == []
    assert sup.observe(doc("clean"), now=1.0) == []
    assert act.calls == [] and sup.journal == []


def test_action_fires_only_after_hysteresis_windows():
    act = Recorder()
    sup = mk(act)
    n = constants.get("supervisor_hysteresis_windows")
    for i in range(n - 1):
        assert sup.observe(doc("rank-dead", dead=[2]), now=float(i)) == []
    out = sup.observe(doc("rank-dead", dead=[2]), now=float(n))
    assert [e["action"] for e in out] == [A_EVICT]
    assert out[0]["windows"] == n and out[0]["ranks"] == [2]
    assert act.calls == [("evict", [2], "rank-dead")]


def test_hysteresis_knob_steers(monkeypatch):
    constants.set("supervisor_hysteresis_windows", 1)
    act = Recorder()
    sup = mk(act)
    out = sup.observe(doc("rank-dead", dead=[5]), now=0.0)
    assert [e["action"] for e in out] == [A_EVICT]


def test_verdict_change_resets_the_streak():
    act = Recorder()
    sup = mk(act)
    sup.observe(doc("rank-dead", dead=[2]), now=0.0)
    sup.observe(doc("rank-dead", dead=[2]), now=1.0)
    sup.observe(doc("straggler"), now=2.0)  # flap: streak restarts
    out = sup.observe(doc("rank-dead", dead=[2]), now=3.0)
    assert out == [] and act.calls == []


# ---------------------------------------------------------------------------
# bounded retries + jittered backoff + escalation
# ---------------------------------------------------------------------------


def _drive_until(sup, d, t0, t1, step=1.0):
    out = []
    t = t0
    while t <= t1:
        out += sup.observe(d, now=t)
        t += step
    return out


def test_backoff_gates_the_second_attempt():
    constants.set("supervisor_backoff_base_s", 5.0)
    act = Recorder()
    sup = mk(act, seed=7)
    d = doc("rank-dead", dead=[2])
    n = constants.get("supervisor_hysteresis_windows")
    entries = _drive_until(sup, d, 0.0, float(n) - 1)
    assert len(entries) == 1
    t_act = entries[0]["time"]
    # inside the backoff window (>= base * 0.5 jitter floor): gated
    assert sup.observe(d, now=t_act + 2.0) == []
    # well past the cap of one base period: the bounded retry fires
    out = sup.observe(d, now=t_act + 10.0)
    assert [e["attempt"] for e in out] == [2]


def test_exhausted_evictions_escalate_to_rollback():
    act = Recorder(ok=False)  # every eviction FAILS
    sup = mk(act, seed=3)
    d = doc("rank-dead", dead=[2])
    entries = _drive_until(sup, d, 0.0, 400.0, step=1.0)
    actions = [e["action"] for e in entries]
    retries = constants.get("supervisor_max_retries")
    assert actions[:retries] == [A_EVICT] * retries
    assert A_ROLLBACK in actions
    # the rollback rung fires ONCE even though its actuation failed
    # attempts are bounded by max_retries per rung too
    assert actions.count(A_ROLLBACK) <= retries
    assert all(e["escalated"] for e in entries if e["action"] == A_ROLLBACK)


def test_rollback_fires_at_most_once_when_applied():
    act = Recorder()
    sup = mk(act, seed=1)
    d = doc("resize-torn")
    entries = _drive_until(sup, d, 0.0, 200.0)
    assert [e["action"] for e in entries] == [A_ROLLBACK]
    assert sup.rolled_back
    assert act.calls == [("rollback", [], "resize-torn")]


def test_clean_streak_resets_the_ladder():
    act = Recorder()
    sup = mk(act, seed=2)
    d = doc("rank-dead", dead=[2])
    n = constants.get("supervisor_hysteresis_windows")
    _drive_until(sup, d, 0.0, float(n))       # one eviction
    _drive_until(sup, doc("clean"), 10.0, 10.0 + n)  # recovery holds
    # a LATER death of a different rank is a fresh episode: primary
    # rung again, not a continuation toward escalation
    d2 = doc("rank-dead", dead=[3])
    entries = _drive_until(sup, d2, 100.0, 100.0 + n)
    assert [e["action"] for e in entries] == [A_EVICT]
    assert entries[0]["attempt"] == 1 and not entries[0]["escalated"]


def test_journal_is_deterministic_per_seed():
    def run(seed):
        sup = mk(Recorder(ok=False), seed=seed)
        out = []
        t = 0.0
        while t < 120.0:
            out += sup.observe(doc("rank-dead", dead=[2]), now=t)
            t += 1.0
        return out

    assert json.dumps(run(11)) == json.dumps(run(11))
    a, b = run(11), run(12)  # different jitter, same ladder shape
    assert [e["action"] for e in a] == [e["action"] for e in b]
    assert [e["time"] for e in a] != [e["time"] for e in b]


# ---------------------------------------------------------------------------
# target selection + quarantine + grow-back
# ---------------------------------------------------------------------------


def test_hang_targets_dead_ranks_else_oldest_stuck():
    act = Recorder()
    constants.set("supervisor_hysteresis_windows", 1)
    sup = mk(act)
    out = sup.observe(
        doc("hang", dead=[3], stuck=[{"rank": 1, "t_issue": 5.0}]),
        now=0.0,
    )
    assert out[0]["ranks"] == [3]  # the corpse, not the waiter
    sup2 = mk(act)
    out = sup2.observe(
        doc("hang", stuck=[{"rank": 2, "t_issue": 9.0},
                           {"rank": 1, "t_issue": 5.0}]),
        now=0.0,
    )
    assert out[0]["ranks"] == [1]  # true deadlock: single oldest waiter


def test_straggler_quarantine_and_cooldown_expiry():
    constants.set("supervisor_hysteresis_windows", 1)
    constants.set("supervisor_quarantine_cooldown_s", 10.0)
    act = Recorder()
    sup = mk(act)
    d = doc("straggler",
            stragglers={"significant": True,
                        "ranking": [{"rank": 7, "mean_lag_ms": 80.0}]})
    out = sup.observe(d, now=0.0)
    assert out[0]["action"] == A_QUARANTINE and out[0]["ranks"] == [7]
    assert 7 in sup.quarantined
    sup.observe(doc("clean"), now=5.0)
    assert 7 in sup.quarantined   # cooldown still covers it
    sup.observe(doc("clean"), now=11.0)
    assert 7 not in sup.quarantined  # denylist expired


def test_grow_back_is_opt_in_and_waits_for_clean():
    constants.set("supervisor_grow_back", True)
    constants.set("supervisor_hysteresis_windows", 2)
    act = Recorder()
    sup = mk(act, policy=default_policy())
    # a 4-rank fleet loses rank 2
    sup.observe(doc("rank-dead", ranks=[0, 1, 2, 3], dead=[2]), now=0.0)
    sup.observe(doc("rank-dead", ranks=[0, 1, 2, 3], dead=[2]), now=1.0)
    assert ("evict", [2], "rank-dead") in act.calls
    out = sup.observe(doc("clean", ranks=[0, 1, 3]), now=2.0)
    assert out == []  # one clean window is not recovery yet
    out = sup.observe(doc("clean", ranks=[0, 1, 3]), now=3.0)
    assert [e["action"] for e in out] == [A_GROW]
    # back at the high-water: no further grow requests
    out = sup.observe(doc("clean", ranks=[0, 1, 3, 4]), now=50.0)
    assert out == []


def test_default_policy_has_no_grow_back_and_no_ps_rule():
    p = default_policy()
    assert "clean" not in p and "ps-overload" not in p


def test_dry_run_journals_but_never_actuates():
    constants.set("supervisor_hysteresis_windows", 1)
    act = Recorder()
    sup = mk(act, dry_run=True)
    out = sup.observe(doc("rank-dead", dead=[2]), now=0.0)
    assert out[0]["result"] == "dry-run"
    assert act.calls == []
    assert sup.counters == {f"{A_EVICT}:dry-run": 1}


def test_already_evicted_ranks_are_not_retargeted():
    constants.set("supervisor_hysteresis_windows", 1)
    constants.set("supervisor_backoff_base_s", 0.1)
    act = Recorder()
    sup = mk(act, seed=5)
    sup.observe(doc("rank-dead", dead=[2]), now=0.0)
    # verdict persists one more window (the aggregator hasn't dropped
    # the view yet): the retry must not re-kill rank 2
    sup.observe(doc("rank-dead", dead=[2]), now=5.0)
    evicts = [c for c in act.calls if c[0] == "evict"]
    assert evicts == [("evict", [2], "rank-dead")]


# ---------------------------------------------------------------------------
# the checkpoint registry
# ---------------------------------------------------------------------------


def test_registry_names_the_newest_artifact(tmp_path, monkeypatch):
    sf = tmp_path / "last.json"
    monkeypatch.setenv(checkpoints.STATE_ENV, str(sf))
    checkpoints._reset_for_tests()
    assert checkpoints.last_checkpoint() is None
    assert "none registered" in checkpoints.describe_last()
    checkpoints.register_checkpoint(tmp_path / "ck", 4)
    rec = checkpoints.last_checkpoint()
    assert rec["step"] == 4
    assert str(tmp_path / "ck") in checkpoints.describe_last()
    # a LATE save of an OLDER step must not roll the pointer back
    checkpoints.register_checkpoint(tmp_path / "old", 2)
    assert checkpoints.last_checkpoint()["step"] == 4
    # the state file mirrors the fact for other processes
    assert json.loads(sf.read_text())["step"] == 4


def test_registry_reads_a_newer_cross_process_record(tmp_path,
                                                     monkeypatch):
    sf = tmp_path / "last.json"
    monkeypatch.setenv(checkpoints.STATE_ENV, str(sf))
    checkpoints._reset_for_tests()
    checkpoints.register_checkpoint(tmp_path / "mine", 3)
    # another process registered step 9
    sf.write_text(json.dumps(
        {"path": str(tmp_path / "theirs"), "step": 9, "time": 0.0}
    ))
    assert checkpoints.last_checkpoint()["step"] == 9
    assert "step 9" in checkpoints.describe_last()


def test_dataloss_messages_name_the_artifact(tmp_path, monkeypatch):
    from torchmpi_tpu.reshard import elastic as E

    checkpoints._reset_for_tests()
    checkpoints.register_checkpoint(tmp_path / "ck.npz", 12)

    class FakeView:
        epoch = 7
        prev = [0, 1, 2]

        def mids(self):
            return [0, 1]

    fake = E.ElasticMember.__new__(E.ElasticMember)
    with pytest.raises(E.DataLoss) as ei:
        # mixed committed layouts: the first fatal branch, reached
        # before any member machinery is touched
        E.ElasticMember._redistribute(
            fake, FakeView(), {"was": [3, 4]}, {0, 1}, {},
        )
    msg = str(ei.value)
    assert "restore from checkpoint" in msg
    assert str(tmp_path / "ck.npz") in msg and "step 12" in msg


def test_zero1_checkpoint_roundtrip_registers(tmp_path, monkeypatch):
    import numpy as np

    from torchmpi_tpu.reshard import elastic as E

    monkeypatch.setenv(checkpoints.STATE_ENV,
                       str(tmp_path / "last.json"))
    checkpoints._reset_for_tests()
    p = tmp_path / "ck.npz"
    E.save_zero1_checkpoint(p, np.arange(8, dtype=np.float32), 6)
    got = E.load_zero1_checkpoint(p)
    assert got["step"] == 6
    assert got["params"].tolist() == list(range(8))
    assert checkpoints.last_checkpoint()["step"] == 6
    assert E.load_zero1_checkpoint(tmp_path / "missing.npz") is None


# ---------------------------------------------------------------------------
# live-plane surfaces: /actions, tm_supervisor_*, mark_evicted
# ---------------------------------------------------------------------------


def test_aggregator_mark_evicted_drops_the_view(tmp_path):
    from torchmpi_tpu.telemetry.live import FleetAggregator

    t = [100.0]
    agg = FleetAggregator(clock=lambda: t[0], stale_after_s=1.0,
                          mark_dir=tmp_path)
    agg.ingest({"kind": "full", "rank": 1, "time": 100.0, "metrics": {}})
    (tmp_path / "dead_rank_1.json").write_text("{}")
    t[0] = 105.0
    assert agg.evaluate()["verdict"] == "rank-dead"
    agg.mark_evicted(1)
    assert agg.evaluate()["verdict"] == "clean"
    assert 1 not in agg.ranks
    # the deliberate eviction retracts the dead-rank marker too
    assert not (tmp_path / "dead_rank_1.json").exists()


def test_actions_endpoint_and_supervisor_metrics():
    from torchmpi_tpu.telemetry.live import FleetAggregator

    constants.set("supervisor_hysteresis_windows", 1)
    agg = FleetAggregator(clock=lambda: 0.0)
    sup = mk(Recorder())
    sup.observe(doc("rank-dead", dead=[2]), now=0.0)
    agg.attach_supervisor(sup)
    agg.serve()
    try:
        base = f"http://127.0.0.1:{agg.http_port}"
        acts = json.loads(urllib.request.urlopen(
            base + "/actions", timeout=10).read().decode())
        assert acts["journal"][0]["action"] == A_EVICT
        assert acts["policy"]["rank-dead"]["escalate"] == A_ROLLBACK
        prom = urllib.request.urlopen(
            base + "/metrics", timeout=10).read().decode()
        assert ('tm_supervisor_actions_total{action="evict-shrink",'
                'result="applied"} 1') in prom
        assert "tm_supervisor_quarantined_ranks 0" in prom
        assert "tm_supervisor_rolled_back 0" in prom
    finally:
        agg.close()


def test_actions_endpoint_404_without_supervisor():
    from torchmpi_tpu.telemetry.live import FleetAggregator

    agg = FleetAggregator(clock=lambda: 0.0)
    agg.serve()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{agg.http_port}/actions", timeout=10
            )
        assert ei.value.code == 404
    finally:
        agg.close()


def test_supervisor_actions_land_in_the_flight_recorder():
    from torchmpi_tpu import telemetry
    from torchmpi_tpu.telemetry import flightrecorder as _flight

    constants.set("supervisor_hysteresis_windows", 1)
    telemetry.enable()
    _flight.enable()
    # the recorder is the process's: whatever file ran before this one on
    # the same worker may have left supervisor entries in it
    _flight.recorder.reset()
    try:
        sup = mk(Recorder())
        sup.observe(doc("rank-dead", dead=[2]), now=0.0)
        entries = [
            e for e in _flight.recorder.snapshot()["entries"]
            if e["comm"] == "supervisor"
        ]
        assert entries and entries[0]["op"] == "supervise.evict-shrink"
        assert entries[0]["routing"] == "verdict=rank-dead"
    finally:
        telemetry.disable()
