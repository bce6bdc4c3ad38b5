"""The reader of ``moe_compact_share`` (``layer_metrics/moe_compact_share.py``):
listed by the traced rehearsal of the cell whose program has the gauge, and
None, so that the line leaves the metric out, for a program without it (the
other three cells, and the parent of the PR that added the compact tier).

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import configs, run as bench  # noqa: E402

CELL = "smallthinker-21b-a3b.stream.x1"
GAUGE = "tm_moe_compact_layers_last_step"


def reader():
    return configs.load_module(
        ROOT / "benchmark" / "layer_metrics" / "moe_compact_share.py")


def test_the_cells_traced_rehearsal_lists_the_metric(capsys):
    rc = bench.main(["--workload", CELL, "--seed", str(2**31 + 29),
                     "--trace", "1", "--rehearse"])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True, out
    assert "moe_compact_share" in line["rehearsed"]
    # at the rehearsal's 336 routes a layer there is no compact tier: the
    # gauge is there and reads no layer, the share 0 (the cell's own size
    # has a tier of 24,576 rows for 98,304 routes; PERF.md has the chip's)
    assert reader().read({"cfg": {"num_hidden_layers": 4}}) == 0.0


def test_a_program_without_the_gauge_gives_none(monkeypatch):
    from torchmpi_tpu.telemetry import metrics

    real = metrics.snapshot
    monkeypatch.setattr(metrics, "snapshot", lambda *a, **kw: {
        k: v for k, v in real(*a, **kw).items() if k != GAUGE})
    for config in ("gpt2-medium", "resnet50-224", "smallthinker-21b-a3b"):
        cfg = configs.load(config, rehearse=True)
        assert reader().read({"cfg": cfg}) is None


def test_the_share_is_the_gauge_over_the_configurations_layers():
    from torchmpi_tpu.telemetry import metrics

    metrics.gauge(GAUGE).set(3.0)
    assert reader().read({"cfg": {"num_hidden_layers": 4}}) == 75.0
    assert reader().read({"cfg": {}}) is None
