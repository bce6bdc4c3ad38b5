"""The inference-serving tier (torchmpi_tpu.serve) and its autoscaling
loop: brownout ladder, atomic weight swaps, REQUEST/REPLY transport
frames, the launch --supervise footgun guard, the aggregator's load
verdicts, the supervisor's scale rungs, and the simulated serving
scenarios (traffic_surge contract, oscillating-trace flap damping).

Everything host-side and clock-injected — the same determinism contract
the supervise/sim suites rely on."""

from __future__ import annotations

import argparse
import json

import numpy as np
import pytest

from torchmpi_tpu import constants
from torchmpi_tpu.serve import (
    InferenceServer,
    ServeClient,
    ShedError,
    WeightCache,
    brownout_level,
    shed_qos_floor,
    version_vector,
)


# ---------------------------------------------------------------------------
# the pure ladder (shared with sim.fleet.SimServe)
# ---------------------------------------------------------------------------


def test_brownout_level_boundaries():
    assert brownout_level(0, 256) == 0
    assert brownout_level(255, 256) == 0
    assert brownout_level(256, 256) == 1
    assert brownout_level(511, 256) == 1
    assert brownout_level(512, 256) == 2
    assert brownout_level(10_000, 256) == 2
    assert brownout_level(10_000, 0) == 0  # budget 0 disables the ladder


def test_shed_qos_floor_ladder():
    # level 0 serves everything; level 1 sheds class 0 only; level 2
    # sheds everything below the top class
    assert shed_qos_floor(0, 3) == 0
    assert shed_qos_floor(1, 3) == 1
    assert shed_qos_floor(2, 3) == 2
    assert shed_qos_floor(1, 1) == 0  # one class: nothing below the top
    assert shed_qos_floor(2, 1) == 0


# ---------------------------------------------------------------------------
# WeightCache: version-vector swap semantics
# ---------------------------------------------------------------------------


def test_weight_cache_swaps_only_on_vector_change():
    t = [100.0]
    cache = WeightCache(np.zeros(4, np.float32), (0, 0),
                        clock=lambda: t[0])
    w, vec = cache.get()
    assert vec == (0, 0) and cache.swaps == 0
    assert not cache.swap(np.ones(4, np.float32), (0, 0))  # same vector
    assert cache.get()[0].sum() == 0.0  # no-op kept the old snapshot
    t[0] = 105.0
    assert cache.swap(np.ones(4, np.float32), (1, 0))
    assert cache.swaps == 1 and cache.versions == (1, 0)
    assert cache.get()[0].sum() == 4.0
    t[0] = 107.5
    assert cache.age_s() == pytest.approx(2.5)


def test_version_vector_tracks_applied_updates():
    import torchmpi_tpu as mpi
    from torchmpi_tpu.parameterserver import ParameterServer, free_all

    mpi.start()
    try:
        ps = ParameterServer(np.zeros(8, np.float32))
        v0 = version_vector(ps)
        ps.send(np.ones(8, np.float32), rule="add").wait()
        v1 = version_vector(ps)
        assert v1 != v0
        assert all(b >= a for a, b in zip(v0, v1))
        srv = InferenceServer(lambda w, x: x, ps)
        assert srv.cache.versions == v1  # seeded from the live vector
        ps.send(np.ones(8, np.float32), rule="add").wait()
        assert srv.refresh_once()        # new vector -> swap
        assert not srv.refresh_once()    # unchanged vector -> no-op
        assert srv.cache.swaps == 1
        np.testing.assert_allclose(srv.cache.get()[0], 2.0)
    finally:
        free_all()


def test_refresh_rides_the_configured_read_policy_and_stays_fresh():
    """The background refresher fetches under serve_refresh_read_policy
    (default 'replica': spread over the chains, off the owner's back)
    — and freshness is PRESERVED: the swap still lands with the
    post-write version vector and the post-write weights, because the
    vector key is chain-consistent and the RYW floor redirects a
    too-stale member to the owner."""
    import torchmpi_tpu as mpi
    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver import ParameterServer, free_all

    assert constants.get("serve_refresh_read_policy") == "replica"
    mpi.start()
    try:
        ps = ParameterServer(np.zeros(8, np.float32))
        seen = []
        orig = ps.receive

        def receive(client=0, read_policy=None):
            seen.append(read_policy)
            return orig(client, read_policy=read_policy)

        ps.receive = receive
        srv = InferenceServer(lambda w, x: x, ps)
        ps.send(np.ones(8, np.float32), rule="add").wait()
        assert srv.refresh_once()
        # the refresh fetch carried the configured policy...
        assert seen[-1] == "replica"
        # ...and the swap installed the post-write view (fresh)
        np.testing.assert_allclose(srv.cache.get()[0], 1.0)
        assert srv.cache.versions == version_vector(ps)
    finally:
        free_all()


# ---------------------------------------------------------------------------
# InferenceServer.handle: the request path + brownout shedding
# ---------------------------------------------------------------------------


def _srv(weights=(1.0, 2.0)):
    return InferenceServer(
        lambda w, x: x + np.float32(w.sum()),
        weights=np.asarray(weights, np.float32),
    )


def test_handle_answers_from_the_snapshot():
    srv = _srv()
    status, y = srv.handle(
        "infer", 0, np.array([10.0], np.float32).tobytes(), pending=0
    )
    assert status == "ok"
    np.testing.assert_allclose(y, [13.0])
    assert srv.served == 1 and srv.shed == 0


def test_handle_sheds_by_qos_at_brownout_levels():
    constants.set("serve_queue_budget", 4)
    srv = _srv()
    x = np.array([1.0], np.float32).tobytes()
    retry = int(constants.get("serve_shed_retry_ms"))
    # level 1 (pending == budget): class 0 shed with a retry hint,
    # class 1 served
    status, y = srv.handle("infer", 0, x, pending=4)
    assert status == f"shed:{retry}" and y is None
    assert srv.handle("infer", 1, x, pending=4)[0] == "ok"
    # level 2 (pending == 2x budget): only the top class survives
    assert srv.handle("infer", 1, x, pending=8)[0] == f"shed:{retry}"
    assert srv.handle("infer", 2, x, pending=8)[0] == "ok"
    assert srv.level == 2 and srv.shed == 2


def test_server_requires_weights_or_ps():
    with pytest.raises(ValueError):
        InferenceServer(lambda w, x: x)


# ---------------------------------------------------------------------------
# REQUEST/REPLY frames over the real listener
# ---------------------------------------------------------------------------


def test_request_reply_round_trip_over_the_wire():
    from torchmpi_tpu.parameterserver import transport as T

    constants.set("serve_queue_budget", 4)
    srv = _srv(weights=(5.0,))
    lst = T._Listener(lambda i: None)
    lst.request_handler = srv.handle
    ch = T._PeerChannel({0: ("127.0.0.1", lst.port)}, 0)
    try:
        x = np.array([1.0, 2.0], np.float32)
        status, y = ch.request(
            T._KIND_REQUEST, 0, 2, 0, rule="infer",
            payload_raw=x.tobytes(),
        )
        assert status == "ok"
        # request payloads ship verbatim (never wire-quantized): the
        # reply is bit-exact float32 math on the exact input
        np.testing.assert_array_equal(y, x + np.float32(5.0))
    finally:
        ch.close()
        lst.close()


def test_surge_over_the_wire_sheds_by_class_and_drops_nothing():
    """A burst held behind a closed model answers every REQUEST exactly
    once, right or as an explicit ``shed:<ms>``: the ladder engages, the
    top class is still served through it, and one request at a time
    afterwards sheds nothing. No clock: the burst is released only once
    the listener has admitted all of it."""
    import threading
    import time

    from torchmpi_tpu.parameterserver import transport as T

    constants.set("serve_queue_budget", 8)
    levels = int(constants.get("serve_qos_levels"))
    gate = threading.Event()

    def model(w, x):
        gate.wait(60)
        return x + w[0]

    srv = InferenceServer(model, weights=np.array([7.0], np.float32))
    lst = T._Listener(lambda i: None)
    lst.request_handler = srv.handle
    ch = T._PeerChannel({0: ("127.0.0.1", lst.port)}, 0)

    def ask(i):
        return ch.submit(
            T._KIND_REQUEST, 0, i % levels, 0, rule="infer",
            payload_raw=np.array([i], np.float32).tobytes())

    try:
        burst = [ask(i) for i in range(48)]
        while lst._pending_frames < len(burst):
            time.sleep(0.001)
        gate.set()
        replies = [ch.complete(w) for w in burst]
        calm = [ch.complete(ask(i)) for i in range(len(burst), 60)]
    finally:
        gate.set()
        ch.close()
        lst.close()
    shed = [i for i, (status, _) in enumerate(replies)
            if status.startswith("shed:")]
    for i, (status, y) in enumerate(replies + calm):
        if i not in shed:  # neither dropped nor wrong
            assert status == "ok" and float(y[0]) == i + 7.0, (i, status)
    assert shed and all(i % levels < levels - 1 for i in shed)
    assert srv.shed == len(shed) and srv.served == 60 - len(shed)
    assert all(status == "ok" for status, _ in calm)


def test_request_without_handler_is_a_loud_error():
    from torchmpi_tpu.parameterserver import transport as T

    lst = T._Listener(lambda i: None)  # no request_handler installed
    ch = T._PeerChannel({0: ("127.0.0.1", lst.port)}, 0)
    try:
        with pytest.raises(RuntimeError, match="request handler"):
            ch.request(T._KIND_REQUEST, 0, 0, 0, rule="infer",
                       payload_raw=b"\x00\x00\x80?")
    finally:
        ch.close()
        lst.close()


class _FakeServeTransport:
    def __init__(self, replies):
        self.replies = list(replies)
        self.calls = 0

    def serve_request(self, proc, rule, payload, qos=0):
        self.calls += 1
        return self.replies.pop(0) if self.replies else ("shed:10", None)


def test_serve_client_honors_retry_hint_then_raises():
    sleeps = []
    tr = _FakeServeTransport([("shed:40", None),
                              ("ok", np.array([7.0], np.float32))])
    c = ServeClient(tr, 0, sleep=sleeps.append)
    out = c.infer(np.array([1.0], np.float32))
    np.testing.assert_allclose(out, [7.0])
    # one shed -> one jittered sleep inside +-50% of the 40ms hint
    assert len(sleeps) == 1 and 0.02 <= sleeps[0] <= 0.06
    with pytest.raises(ShedError):
        ServeClient(_FakeServeTransport([]), 0,
                    sleep=lambda s: None).infer(
            np.array([1.0], np.float32), max_sheds=2
        )


# ---------------------------------------------------------------------------
# launch --supervise footgun: the supervisor must never starve silently
# ---------------------------------------------------------------------------


def test_supervise_auto_arms_the_live_plane():
    from torchmpi_tpu.launch import arm_supervise_telemetry

    args = argparse.Namespace(supervise=True, telemetry_live=False)
    notice = arm_supervise_telemetry(args)
    assert args.telemetry_live is True
    assert notice and "--telemetry-live" in notice and "auto-arm" in notice


def test_supervise_arm_is_a_noop_when_already_armed_or_unsupervised():
    from torchmpi_tpu.launch import arm_supervise_telemetry

    armed = argparse.Namespace(supervise=True, telemetry_live=True)
    assert arm_supervise_telemetry(armed) is None
    plain = argparse.Namespace(supervise=False, telemetry_live=False)
    assert arm_supervise_telemetry(plain) is None
    assert plain.telemetry_live is False


# ---------------------------------------------------------------------------
# load verdicts: SLO burn / queue growth / BUSY trend -> overload,
# traffic collapse -> underload (incremental, windowed)
# ---------------------------------------------------------------------------


def _serve_frame(agg, rank, t, requests=0.0, shed=0.0, breaches=0.0,
                 queue=0.0, busy=None):
    met = {
        "tm_serve_requests_total": {"series": {
            "result=ok": requests, "result=shed": shed,
        }},
        "tm_serve_slo_breaches_total": {"series": {"": breaches}},
        "tm_serve_queue_depth": {"series": {"": queue}},
    }
    if busy is not None:
        met["tm_ps_busy_rejected_total"] = {"series": busy}
    agg.ingest({"kind": "full", "rank": rank, "time": t, "metrics": met,
                "seq_high_water": {}, "flight_tail": []})


def test_slo_burn_trips_the_overload_verdict():
    from torchmpi_tpu.telemetry import live

    agg = live.FleetAggregator(clock=lambda: 0.0, stale_after_s=1e9)
    _serve_frame(agg, 0, 1000.0, requests=100.0)
    assert agg.evaluate(now=1000.0)["verdict"] == "clean"  # baseline
    _serve_frame(agg, 0, 1002.0, requests=200.0, breaches=30.0)
    doc = agg.evaluate(now=1002.0)
    assert doc["verdict"] == "overload"
    assert doc["load"]["slo_burn"] == pytest.approx(0.3)
    assert doc["load"]["overload"] and not doc["load"]["underload"]
    assert any("overload" in s for s in doc["summary"])


def test_queue_growth_alone_trips_overload():
    from torchmpi_tpu.telemetry import live

    agg = live.FleetAggregator(clock=lambda: 0.0, stale_after_s=1e9)
    _serve_frame(agg, 0, 1000.0, requests=10.0, queue=0.0)
    agg.evaluate(now=1000.0)
    _serve_frame(agg, 0, 1002.0, requests=20.0, queue=500.0)
    doc = agg.evaluate(now=1002.0)
    assert doc["verdict"] == "overload"
    assert doc["load"]["queue_growth_per_s"] == pytest.approx(250.0)


def test_traffic_collapse_reads_as_underload():
    from torchmpi_tpu.telemetry import live

    agg = live.FleetAggregator(clock=lambda: 0.0, stale_after_s=1e9)
    _serve_frame(agg, 0, 1000.0, requests=1000.0)
    agg.evaluate(now=1000.0)
    _serve_frame(agg, 0, 1002.0, requests=1001.0)  # ~0.5 qps/rank
    doc = agg.evaluate(now=1002.0)
    assert doc["verdict"] == "underload"
    assert doc["load"]["underload"] and doc["load"]["qps_per_rank"] < 1.0


def test_training_only_fleets_never_see_load_verdicts():
    from torchmpi_tpu.telemetry import live

    agg = live.FleetAggregator(clock=lambda: 0.0, stale_after_s=1e9)
    # busy rejections but NO tm_serve_* family: a training-only fleet
    agg.ingest({"kind": "full", "rank": 0, "time": 1000.0,
                "metrics": {"tm_ps_busy_rejected_total": {
                    "series": {"listener=l0": 50.0}}},
                "seq_high_water": {}, "flight_tail": []})
    agg.evaluate(now=1000.0)
    agg.ingest({"kind": "full", "rank": 0, "time": 1002.0,
                "metrics": {"tm_ps_busy_rejected_total": {
                    "series": {"listener=l0": 90.0}}},
                "seq_high_water": {}, "flight_tail": []})
    doc = agg.evaluate(now=1002.0)
    assert doc["load"] is None
    assert doc["verdict"] not in ("overload", "underload")


def test_ps_health_reports_per_listener_busy_rate_trend():
    from torchmpi_tpu.telemetry.analyze import ps_health

    def ranks(busy):
        return {0: {"snapshot": {"metrics": {
            "tm_ps_busy_rejected_total": {"series": busy},
        }, "flight_recorder": {"entries": []}}}}

    first = ps_health(ranks({"listener=l0": 100.0, "listener=l1": 10.0}))
    srv = first["servers"]["0"]
    assert srv["busy_by_listener"] == {"l0": 100.0, "l1": 10.0}
    assert "busy_rate_per_s" not in srv  # no window yet: integral only
    second = ps_health(
        ranks({"listener=l0": 160.0, "listener=l1": 10.0}),
        prev=first["servers"], interval_s=2.0,
    )
    rates = second["servers"]["0"]["busy_rate_per_s"]
    # the TREND: l0 is rejecting NOW (30/s), l1's integral is history
    assert rates == {"l0": 30.0, "l1": 0.0}


# ---------------------------------------------------------------------------
# supervisor scale rungs: hysteresis, shared cooldown, world bounds
# ---------------------------------------------------------------------------


from torchmpi_tpu.supervise import (  # noqa: E402
    A_SCALE_DOWN,
    A_SCALE_UP,
    RecoverySupervisor,
)
from torchmpi_tpu.supervise.core import Actuator  # noqa: E402


class ScaleRecorder(Actuator):
    """Default delegation under test: scale_up -> grow, scale_down ->
    evict (an actuator that can grow/evict can already scale)."""

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


def _doc(verdict, ranks=(0, 1, 2, 3)):
    return {"verdict": verdict, "ranks": list(ranks), "dead_ranks": [],
            "stuck": [], "stragglers": {}, "resize": {}}


def test_scale_up_fires_after_its_hysteresis_and_delegates_to_grow():
    act = ScaleRecorder()
    sup = RecoverySupervisor(act, clock=lambda: 0.0)
    n = int(constants.get("supervisor_scale_up_hysteresis"))
    for i in range(n - 1):
        assert sup.observe(_doc("overload"), now=float(i)) == []
    out = sup.observe(_doc("overload"), now=float(n))
    assert [e["action"] for e in out] == [A_SCALE_UP]
    assert out[0]["ranks"] == [] and out[0]["windows"] == n
    assert act.calls == [("grow", [], "overload")]


def test_scale_down_is_slower_and_retires_the_highest_rank():
    act = ScaleRecorder()
    sup = RecoverySupervisor(act, clock=lambda: 0.0)
    up = int(constants.get("supervisor_scale_up_hysteresis"))
    down = int(constants.get("supervisor_scale_down_hysteresis"))
    assert down > up  # the asymmetry IS the first line of flap damping
    for i in range(down - 1):
        assert sup.observe(_doc("underload"), now=float(i)) == []
    out = sup.observe(_doc("underload"), now=float(down))
    assert [e["action"] for e in out] == [A_SCALE_DOWN]
    assert out[0]["ranks"] == [3]  # the world contracts from the top
    assert act.calls == [("evict", [3], "underload")]


def test_shared_cooldown_gates_any_second_scale_action():
    constants.set("supervisor_scale_up_hysteresis", 1)
    constants.set("supervisor_scale_down_hysteresis", 1)
    constants.set("supervisor_scale_cooldown_s", 30.0)
    constants.set("supervisor_backoff_base_s", 0.0)
    act = ScaleRecorder()
    sup = RecoverySupervisor(act, clock=lambda: 0.0)
    assert sup.observe(_doc("overload"), now=0.0) != []
    # the cooldown is SHARED across both rungs: an underload right after
    # a scale-up must not saw the world back down
    assert sup.observe(_doc("underload"), now=5.0) == []
    assert sup.observe(_doc("underload"), now=10.0) == []
    out = sup.observe(_doc("underload"), now=31.0)
    assert [e["action"] for e in out] == [A_SCALE_DOWN]
    assert len(act.calls) == 2


def test_scale_up_holds_at_max_world_for_the_brownout_ladder():
    constants.set("supervisor_scale_up_hysteresis", 1)
    constants.set("supervisor_scale_max_world", 4)
    act = ScaleRecorder()
    sup = RecoverySupervisor(act, clock=lambda: 0.0)
    # at the ceiling: HOLD (the serving brownout ladder degrades
    # gracefully instead of the fleet collapsing under a doomed grow)
    assert sup.observe(_doc("overload", ranks=(0, 1, 2, 3)), now=0.0) == []
    # below it: the rung fires
    assert sup.observe(_doc("overload", ranks=(0, 1, 2)), now=1.0) != []
    assert act.calls == [("grow", [], "overload")]


def test_scale_down_holds_at_min_world():
    constants.set("supervisor_scale_down_hysteresis", 1)
    constants.set("supervisor_scale_min_world", 4)
    act = ScaleRecorder()
    sup = RecoverySupervisor(act, clock=lambda: 0.0)
    assert sup.observe(_doc("underload", ranks=(0, 1, 2, 3)), now=0.0) == []
    assert act.calls == []


# ---------------------------------------------------------------------------
# simulated serving tier: the packaged surge scenario + flap damping
# ---------------------------------------------------------------------------


def test_traffic_surge_scales_up_then_down_without_flapping(tmp_path):
    """The acceptance ladder for the serving tier in one scenario:
    overload (SLO burn + queue growth under a 10x surge) -> scale-up
    through the real coordinator join; brownout shedding with ZERO
    silent drops while saturated; underload after the surge ->
    scale-down back; the resize count bounded by hysteresis+cooldown —
    byte-identical per seed."""
    from torchmpi_tpu.sim import run_scenario

    res = run_scenario("traffic_surge", tmp_path / "a", supervise=True)
    assert res["ok"], res["failures"]
    acts = [e["action"] for e in res["recovery"]["journal"]]
    assert "scale-up" in acts and "scale-down" in acts
    # every scale-down comes AFTER the last scale-up: grow under the
    # surge, shrink after it — never interleaved sawing
    assert acts.index("scale-down") > len(acts) - 1 - acts[::-1].index(
        "scale-up"
    ) - 1
    serve = res["stats"]["serve"]
    assert serve["shed"] > 0 and serve["dropped"] == 0.0
    assert serve["peak_level"] >= 1  # the brownout ladder engaged
    assert res["stats"]["serve"]["swaps"] > 0  # weights kept flowing
    res2 = run_scenario("traffic_surge", tmp_path / "b", supervise=True)
    assert json.dumps(res["recovery"]["journal"], sort_keys=True) == \
        json.dumps(res2["recovery"]["journal"], sort_keys=True)


def test_oscillating_arrivals_do_not_flap_the_world(tmp_path):
    """The scale-down hysteresis contract: a trace sawing between surge
    and idle every 3s (shorter than the 4s underload streak the down
    rung demands) must produce NO scale-down during the oscillation —
    only the long idle tail may shrink — and a bounded resize count."""
    from torchmpi_tpu.sim import run_scenario

    scn = {
        "name": "oscillate",
        "ranks": 16,
        "group_size": 8,
        "steps": 120,
        "seed": 11,
        "horizon_s": 30.0,
        "constants": {
            "elastic_heartbeat_seconds": 0.5,
            "telemetry_live_interval_s": 0.5,
            "watchdog_timeout_seconds": 0,
            "sim_step_seconds": 0.25,
            "supervisor_scale_cooldown_s": 6.0,
            "supervisor_scale_up_hysteresis": 3,
            "supervisor_scale_down_hysteresis": 8,
        },
        "serve": {
            "trace": [
                [0.0, 300.0], [3.0, 0.2], [6.0, 300.0], [9.0, 0.2],
                [12.0, 300.0], [15.0, 0.2], [18.0, 0.2], [30.0, 0.2],
            ],
            "capacity_qps": 120.0,
            "tick_s": 0.25,
        },
        "events": [],
        "expected": {
            "steps_completed_min": 1,
            "recovery": {
                "rollback": False,
                "max_resizes": 7,
                "serve_dropped_max": 0,
            },
        },
    }
    res = run_scenario(scn, tmp_path, supervise=True)
    assert res["ok"], res["failures"]
    downs = [e for e in res["recovery"]["journal"]
             if e["action"] == A_SCALE_DOWN]
    # the saw never shrank the world: every scale-down sits in the
    # long idle tail (>= 18s), past the 8-window underload streak
    assert all(e["time"] >= 18.0 for e in downs)
    assert len(res["stats"]["resizes"]) <= 7
    assert res["stats"]["serve"]["dropped"] == 0.0
