"""The reader of ``attn_index_kernel_ms_per_step`` (``layer_metrics/
attn_index_kernel_ms_per_step.py``): the index-score kernel's events by
name, whatever else ran, None where none did; and the name it holds against
the program's own.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import inspect
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import configs  # noqa: E402

METRIC = "attn_index_kernel_ms_per_step"


def reader():
    return configs.load_module(
        ROOT / "benchmark" / "layer_metrics" / f"{METRIC}.py")


# names as the chip's trace has them: (the events' seconds over 20 traced
# steps, the metric)
CASES = {
    "two_events_among_others": ({
        "%tm_attn_index_scores.7 = f32[4096,16384]{1,0:T(8,128)} custom": 0.16,
        "%tm_attn_index_scores.12 = f32[4096,4096]{1,0:T(8,128)} custom-": 0.04,
        "%tm_attn_index_grad_keys.3 = f32[16384,64]{1,0:T(8,128)} custom": 0.5,
        "%fusion.381 = f32[2048,18992]{0,1:T(8,128)}": 1.0}, 10.0),
    "bare_name": ({"tm_attn_index_scores": 0.4}, 20.0),
    "other_kernels_alone": ({
        "%tm_attn_index_grad_keys.3 = f32[16384,64]{1,0:T(8,128)}": 0.5,
        "%splash_mqa_fwd_residuals.15 = (f32[2,4,512,128]": 0.3}, None),
    "no_events": ({}, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_reader_sums_the_kernels_events_by_name(case):
    op_times, want = CASES[case]
    run = {"steady": {"op_times": op_times, "steps": 20, "devices": 1},
           "phase": {"traced_steps": 20}}
    got = reader().read(run)
    assert got == (None if want is None else pytest.approx(want))


def test_no_traced_step_gives_none():
    run = {"steady": {"op_times": {"%tm_attn_index_scores.7 = f32": 0.2}},
           "phase": {}}
    assert reader().read(run) is None


def test_the_name_is_the_programs_kernels():
    """The reader holds the name itself (the parent of the PR that added it
    reads too): it is the ``name=`` of ``_index_scores``'s ``pallas_call``,
    and no other kernel of the module starts with it."""
    from torchmpi_tpu.parallel import selected_attention as sa

    kernel = reader().KERNEL
    assert f'name="{kernel}"' in inspect.getsource(sa._index_scores)
    assert inspect.getsource(sa).count(f'name="{kernel}') == 1


def test_the_metric_is_the_selecting_cells_alone():
    import json

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in spec["per_layer"] if m["name"] == METRIC)
    assert entry == {
        "name": METRIC, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "attention",
        "moves": "samples_per_s_per_chip",
        "workloads": ["keye-vl-2-30b-a3b.stream.x1"]}
