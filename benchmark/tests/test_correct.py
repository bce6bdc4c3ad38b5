"""What decides ``correct`` has been shown to fail.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

Run at the rehearsal's sizes, which a test run can hold; the readings at
the cells' own sizes on the chip are in PERF.md. Three things are shown:
a sound run is correct; the same run with the timed path broken underneath
(the engine's step returns its state unchanged) is not; and the control,
the plain reference computed with fp8 operands and put in the program's
place, is not.
"""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import run as bench  # noqa: E402

CELLS = ["gpt2-medium.stream.x1", "resnet50-224.resident.x1"]


def drive(capsys, cell):
    """A whole run but for the look for a chip: ``--rehearse``."""
    rc = bench.main(["--workload", cell, "--seed", "41", "--seconds", "1",
                     "--trace", "0", "--rehearse"])
    assert rc == 0
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(capsys, cell):
    line, out = drive(capsys, cell)
    assert line["correct"] is True, out
    assert line["metrics"] == {} and line["attempted"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch, cell):
    from torchmpi_tpu.engine import AllReduceSGDEngine

    real = AllReduceSGDEngine._step_core

    def unchanged(self, params, opt_state, model_state, batch):
        _, _, _, loss = real(self, params, opt_state, model_state, batch)
        return params, opt_state, model_state, loss

    monkeypatch.setattr(AllReduceSGDEngine, "_step_core", unchanged)
    line, out = drive(capsys, cell)
    assert line["correct"] is False, out
    assert "update_norm_gap" in out and "OUTSIDE" in out


@pytest.mark.parametrize("cell", CELLS)
def test_fp8_control_is_not_correct(cell):
    """The cell's own comparison, as ``run.py`` makes it (its mode's
    followed steps, its mode's limits, losses shaped as the mode shows
    them), with the control in the program's place."""
    import torchmpi_tpu as mpi

    from benchmark import check, configs, traffic

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = bench.find_cell(spec, cell)
    jax, devices = bench.bring_up(entry["chips"], rehearse=True)
    cfg = configs.load(entry["config"], rehearse=True)
    built = configs.build(entry["config"], cfg)
    mpi.start(devices=devices)
    try:
        mode = traffic.make(entry["traffic"], cfg, built, entry["chips"], 41,
                            check.CompileLedger(), rehearse=True)
        mode.first_steps()
        followed = mode.followed
        mode.release()
    finally:
        mpi.stop()
    batches = followed.pop("batches")
    params = built.make_state(41)[0]
    ref = check.follow_reference(entry["config"], cfg, params, mode, batches)
    control = check.follow_reference(
        entry["config"], cfg, params, mode, batches, "fp8")
    limits = cfg["limits"][mode.mix["mode"]]
    lines = []
    assert not check.verdict(
        check.compare(control, ref), limits, out=lines.append), lines
    # and the program, and the reference itself, are inside the same limits
    assert check.verdict(
        check.compare(followed, ref), limits, out=lines.append), lines
    assert check.verdict(check.compare(ref, ref), limits, out=lines.append)
