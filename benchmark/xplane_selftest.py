"""Self-test of the trace reduction.

    python3 benchmark/xplane_selftest.py

First the interval arithmetic on events written out by hand, then the whole
reduction on the small trace recorded on the chips that lies beside this
file (``testdata/small.xplane.pb``, recorded by ``testdata/record_small.py``:
four chips, a few steps of a matmul and a psum with the host asleep between
them under ``bench.*`` spans, which lie in ``small.spans.json``), whose
numbers are known from how it was made.
"""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from benchmark import xplane  # noqa: E402


def close(a, b, tol=1e-9):
    return abs(a - b) <= tol


def test_intervals():
    merged = xplane.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert merged == [(0, 3), (5, 7)], merged
    assert close(xplane.length(merged), 5)
    assert xplane.gaps(merged) == [(3, 5)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert xplane.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]


def test_device_summary():
    # a container (while) holding two ops, then a collective half covered
    # by a compute op on the same chip, then an idle gap, then one op
    events = sorted([
        ("while.1", 0.0, 4.0), ("fusion.1", 0.0, 1.5), ("fusion.2", 2.0, 4.0),
        ("all-reduce.3", 4.0, 6.0), ("fusion.4", 5.0, 6.0),
        ("fusion.5", 9.0, 10.0),
    ], key=lambda e: (e[1], -e[2]))
    names = sorted(n for n, _, _ in xplane.leaves(events))
    assert names == ["all-reduce.3", "fusion.1", "fusion.2", "fusion.4",
                     "fusion.5"], names
    d = xplane.device_summary(events)
    assert close(d["busy_s"], 7.0) and close(d["window_s"], 10.0), d
    assert close(d["collective_s"], 2.0), d
    assert close(d["exposed_collective_s"], 1.0), d
    times = xplane.op_times(events)
    assert "while.1" not in times and close(times["fusion.2"], 2.0), times
    assert xplane.step_count(
        [("jit_step", 0, 1), ("jit_step", 2, 3), ("jit_norms", 4, 4.1)]) == 2


def test_attribution():
    busy = [(0.0, 1.0), (3.0, 4.0), (4.5, 5.0), (9.0, 10.0)]
    spans = [("bench.input_wait", 0.9, 2.9), ("bench.dispatch", 2.9, 3.1),
             ("bench.epoch_boundary.loss_read", 5.0, 6.0),
             ("bench.epoch_boundary.first_batch", 6.0, 8.9)]
    by, longest = xplane.attribute(xplane.gaps(busy), spans)
    assert close(by["bench.input_wait"], 2.0), by
    assert close(by["none"], 0.5), by
    assert close(by["bench.epoch_boundary.first_batch"], 4.0), by
    assert longest[0] == ("bench.epoch_boundary.first_batch", 4.0), longest
    # one boundary (two adjacent spans), which touches the 4 s gap only
    assert xplane.boundary_idle(busy, spans) == [4.0]


def test_recorded():
    path = HERE / "testdata" / "small.xplane.pb"
    if not path.exists():
        print("no recorded trace beside the self-test: skipped")
        return
    import json

    expect = json.loads((HERE / "testdata" / "small.expect.json").read_text())
    host = json.loads((HERE / "testdata" / "small.spans.json").read_text())
    r = xplane.reduce(path, [tuple(s) for s in host["spans"]],
                      host["origin_ns"])
    assert r["devices"] == expect["devices"], r["devices"]
    assert r["steps"] == expect["steps"], r["steps"]
    # the host slept between steps under bench.input_wait, and between the
    # two halves under bench.epoch_boundary: the idle time lies there
    lo, hi = expect["idle_input_wait_s"]
    assert lo <= r["idle_by_span"]["bench.input_wait"] <= hi, r["idle_by_span"]
    assert len(r["boundary_idle_s"]) == 1, r["boundary_idle_s"]
    lo, hi = expect["boundary_idle_s"]
    assert lo <= r["boundary_idle_s"][0] <= hi, r["boundary_idle_s"]
    assert 0 < r["busy_s"] < r["window_s"]
    if expect["devices"] > 1:
        assert r["collective_s"] > 0, r["collective_s"]
        assert 0 <= r["exposed_collective_s"] <= r["collective_s"]
    assert any(xplane.is_collective(k) for k in r["op_times"]) == (
        expect["devices"] > 1)
    b = xplane.breakdown(r)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


if __name__ == "__main__":
    for test in (test_intervals, test_device_summary, test_attribution,
                 test_recorded):
        test()
        print(f"ok {test.__name__}")
