"""``test_correct.py``'s three demonstrations for the cell
``lfm2-8b-a1b.stream.x1``, at its rehearsal's sizes: a sound run is
correct, a step that returns its state unchanged is not, and the fp8
control in the program's place is not. The demonstrations themselves are
that file's, loaded by path and given this cell. (More, of this
configuration alone, are in ``tests/test_sconv_decoder.py``: the gated short
convolution against a loop over positions, each block against the
reference's layer, the four shares of experts add up to the uncut
reference's layer, the bias after three steps equal to the reference's, the
tied table's gradient.)

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "benchmark_tests_test_correct", Path(__file__).with_name("test_correct.py"))
shown = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(shown)

CELL = "lfm2-8b-a1b.stream.x1"


def test_sound_run_is_correct(capsys):
    shown.test_sound_run_is_correct(capsys, CELL)


def test_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    shown.test_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch, CELL)


def test_fp8_control_is_not_correct():
    shown.test_fp8_control_is_not_correct(CELL)
