"""Self-test of what the per-layer metrics read of the program's tracing.

    python3 benchmark/scopes_selftest.py

First the arithmetic of ``benchmark/scopes.py`` on spans, operation names
and a protobuf message written out by hand; then the whole reduction on the
trace recorded on the chips that lies beside this file
(``testdata/scoped.xplane.pb`` and the program's own spans in
``scoped.spans.json``, recorded by ``testdata/record_scoped.py``: six steps
of the engine on a four-layer MLP of width 4096 with the host asleep 40 ms before each
batch), whose numbers are known from how it was made.
"""

import json
import sys
from collections import namedtuple
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from benchmark import scopes, xplane  # noqa: E402

Rec = namedtuple("Rec", "name start_ns dur_ns id parent step")


def close(a, b, tol=1e-9):
    return abs(a - b) <= tol


def test_scope_of():
    pre = "jit(tm_step)/shard_map/"
    for op, want in [
        (pre + "tm.fwd_bwd/transpose(jvp(loss))/dot_general:", "tm.fwd_bwd"),
        (pre + "tm.grad_sync/pack/concatenate:", "tm.grad_sync/pack"),
        (pre + "tm.grad_sync/b3/reduce/psum:", "tm.grad_sync/reduce"),
        (pre + "tm.grad_sync/b12/unpack/div:", "tm.grad_sync/unpack"),
        (pre + "tm.grad_sync/psum:", "tm.grad_sync"),
        ("jit(tm_epoch)/shard_map/while/body/tm.optimizer/mul:",
         "tm.optimizer"),
        ("jit(step)/shard_map/psum:", None), ("", None),
    ]:
        assert scopes.scope_of(op) == want, (op, scopes.scope_of(op))


def test_spans():
    origin = 1_000_000_000
    ms = 1_000_000
    records = [
        Rec("engine.input_wait", origin + 10 * ms, 30 * ms, 1, None, (0, 0)),
        Rec("input.ring_wait", origin + 12 * ms, 20 * ms, 2, 1, (0, 0)),
        Rec("input.stage", origin + 33 * ms, 5 * ms, 3, 1, (0, 0)),
        Rec("engine.dispatch", origin + 41 * ms, 4 * ms, 4, None, (0, 0)),
        Rec("engine.input_wait", origin + 50 * ms, 2 * ms, 5, None, (0, 1)),
    ]
    # a parent keeps only what no child covers
    own = scopes.self_time(records, origin)
    wait = [(round(s, 6), round(e, 6)) for n, s, e in own
            if n == "engine.input_wait"]
    assert wait == [(0.010, 0.012), (0.032, 0.033), (0.038, 0.040),
                    (0.050, 0.052)], wait
    assert [n for n, _, _ in own][:2] == ["engine.input_wait",
                                          "input.ring_wait"]
    # a gap goes to the innermost span open in most of it
    by, _ = xplane.attribute([(0.013, 0.031), (0.046, 0.049)], own)
    assert close(by["input.ring_wait"], 0.018) and close(by["none"], 0.003)
    # overlap with a window of the trace, by the wall clock
    inside = scopes.overlapping(records, origin, (0.035, 0.0505))
    assert [r.id for r in inside] == [1, 3, 4, 5], inside
    on_clock = scopes.on_trace_clock(scopes.named(inside, "engine.dispatch"),
                                     origin)
    (name, s, e), = on_clock
    assert name == "engine.dispatch" and close(s, 0.041) and close(e, 0.045)


def test_own_intervals():
    # a while that holds two operations with a gap, a collective that
    # spans the second, then an operation of its own
    events = sorted([
        ("while.1", 0.0, 4.0), ("fusion.1", 0.5, 1.5), ("fusion.2", 2.0, 4.0),
        ("all-reduce.3", 3.0, 6.0), ("fusion.4", 7.0, 8.0),
    ], key=lambda e: (e[1], -e[2]))
    own = dict(scopes.own_intervals(events))
    assert own["while.1"] == [(0.0, 0.5), (1.5, 2.0)], own
    assert own["fusion.1"] == [(0.5, 1.5)] and own["fusion.2"] == [(2.0, 4.0)]
    assert own["all-reduce.3"] == [(3.0, 6.0)] and own["fusion.4"] == [
        (7.0, 8.0)], own


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_wire_format():
    """An XSpace written out by hand: one device plane whose two event
    metadata carry ``tf_op`` as a string and as a reference, and a host
    plane that is passed over."""
    import tempfile

    stat_meta = b"".join(
        _field(5, _field(1, key) + _field(2, _field(1, key)
                                          + _field(2, name.encode())))
        for key, name in [(7, "tf_op"), (9, "jit(f)/tm.optimizer/mul:"),
                          (11, "flops")])

    def event_meta(key, name, stat):
        meta = _field(1, key) + _field(2, name.encode()) + _field(
            5, _field(1, 11) + _field(4, 300)) + _field(5, stat)
        return _field(4, _field(1, key) + _field(2, meta))

    plane = (
        _field(1, 3) + _field(2, b"/device:TPU:0")
        + _field(3, _field(2, b"XLA Ops") + _field(3, 1 << 40))
        + event_meta(1, "%fusion.1 = f32[8]", _field(1, 7) + _field(
            5, b"jit(f)/tm.fwd_bwd/dot_general:"))
        + event_meta(2, "%fusion.2 = f32[8]", _field(1, 7) + _field(7, 9))
        + event_meta(3, "%copy-start = f32[8]", _field(1, 11) + _field(4, 1))
        + stat_meta)
    space = _field(1, _field(2, b"/host:CPU")) + _field(1, plane)
    with tempfile.NamedTemporaryFile(suffix=".pb") as f:
        f.write(space)
        f.flush()
        got = scopes.op_names(f.name)
    assert got == {"/device:TPU:0": {
        "%fusion.1 = f32[8]": "jit(f)/tm.fwd_bwd/dot_general:",
        "%fusion.2 = f32[8]": "jit(f)/tm.optimizer/mul:",
    }}, got


def test_recorded():
    path = HERE / "testdata" / "scoped.xplane.pb"
    if not path.exists():
        print("no recorded trace beside the self-test: skipped")
        return
    expect = json.loads((HERE / "testdata" / "scoped.expect.json").read_text())
    host = json.loads((HERE / "testdata" / "scoped.spans.json").read_text())
    origin = host["origin_ns"]
    records = [Rec(n, s, d, i, p, tuple(st) if st else None)
               for n, s, d, i, p, st in host["spans"]]
    r = scopes.by_scope(str(path))
    assert r["devices"] == expect["devices"], r["devices"]
    assert r["steps"] == expect["steps"], r["steps"]
    # the program's own count of what its sync reduces: the tree, in bytes
    assert expect["sync_bytes_gauge"] == expect["sync_bytes"], expect
    got = set(r["scope_s"])
    want = {"tm.fwd_bwd", "tm.optimizer"}
    if expect["devices"] > 1:
        want |= {"tm.grad_sync/pack", "tm.grad_sync/reduce",
                 "tm.grad_sync/unpack"}
    assert want <= got, got
    # the scopes explain the step: what lies under none is the smaller
    # share, every operation that leads it is one XLA made itself (layout
    # copies, and the dynamic-update-slices it rewrites the flat buffer's
    # concatenate into), which carry no op_name at all; and no scope alone
    # is longer than the busy union
    brief = {k: r[k] for k in ("scope_s", "unscoped_s", "unscoped_ops",
                               "busy_s")}
    assert 0 <= r["unscoped_s"] < 0.2 * r["busy_s"], brief
    for (event, op_name), _ in r["unscoped_ops"]:
        assert op_name == "" and any(
            kind in event for kind in ("copy", "dynamic-update-slice")), event
    assert all(0 < t <= r["busy_s"] for t in r["scope_s"].values()), brief
    # every busy interval is some event's own: the scopes and none add up
    # to the busy union (a collective may overlap compute, so: at least)
    total = sum(r["scope_s"].values()) + r["unscoped_s"]
    assert r["busy_s"] * 0.999 <= total <= r["busy_s"] * 1.5, (total, brief)
    # each scope against what its work needs at the least on a v5e (197
    # TFLOP/s in bf16 passes, 819 GB/s of HBM, 1,600 Gbit/s between
    # chips), and three times that at the most:
    # forward and backward are 6 FLOP a weight and a sample; Adam reads
    # the gradient, both moments and the parameter and writes three back
    per_step = {k: t / r["steps"] for k, t in r["scope_s"].items()}
    least = {
        "tm.fwd_bwd": 6 * expect["weights"] * expect["per_chip"] / 197e12,
        "tm.optimizer": 7 * expect["sync_bytes"] / 819e9,
    }
    p = expect["devices"]
    if p > 1:  # a ring moves 2 (p - 1) / p of the buffer at 1,600 Gbit/s
        least["tm.grad_sync/reduce"] = (
            2 * (p - 1) / p * expect["sync_bytes"] / 200e9)
    for scope, t in least.items():
        assert t <= per_step[scope] <= 3 * t, (scope, t, per_step)
    # the program's spans over the device trace, on one clock: six waits
    # and six dispatches inside the window, and every idle gap longer than
    # 10 ms lies under the engine.input_wait that caused it
    window, busy = r["window"], r["busy"]
    inside = scopes.overlapping(records, origin, window)
    waits = scopes.named(inside, "engine.input_wait")
    assert len(scopes.named(inside, "engine.dispatch")) == expect["steps"]
    assert expect["steps"] - 1 <= len(waits) <= expect["steps"] + 1, waits
    own = scopes.self_time(inside, origin)
    long_gaps = [g for g in xplane.gaps(busy) if g[1] - g[0] > 0.010]
    # one between each two steps, and one more where the engine's
    # broadcast ran inside the window before the first
    assert expect["steps"] - 1 <= len(long_gaps) <= expect["steps"], long_gaps
    for gap in long_gaps:
        by, _ = xplane.attribute([gap], own)
        assert list(by) == ["engine.input_wait"], (gap, by)
        assert gap[1] - gap[0] < expect["sleep_s"] + 0.020, gap
    by, _ = xplane.attribute(xplane.gaps(busy), own)
    idle = xplane.length(xplane.gaps(busy))
    assert by["engine.input_wait"] > 0.8 * idle, (by, idle)


if __name__ == "__main__":
    for test in (test_scope_of, test_spans, test_own_intervals,
                 test_wire_format, test_recorded):
        test()
        print(f"ok {test.__name__}")
