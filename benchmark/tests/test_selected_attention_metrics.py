"""The readers of the selected-attention metrics (``layer_metrics/
attn_selected_pair_share.py``, ``attn_index_loss.py``,
``attn_sparse_kernel_roofline.py``) and the functions behind the roofline
(``sparse_attention_roofline.py``): a value where the program has the gauge
or the kernels, None, so that the line leaves the metric out, where it has
not (the other cells, and the parent of the PR that added them).

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import configs, sparse_attention_roofline as roofline  # noqa: E402

GAUGES = ("tm_attn_selected_pairs_per_step", "tm_attn_causal_pairs_per_step",
          "tm_attn_index_loss_last_step")


def reader(name):
    return configs.load_module(
        ROOT / "benchmark" / "layer_metrics" / f"{name}.py")


def test_the_share_and_the_loss_are_the_gauges():
    from torchmpi_tpu.telemetry import metrics

    for name, value in zip(GAUGES, (31_458_304.0 * 4, 134_225_920.0 * 4,
                                    0.07)):
        metrics.gauge(name).set(value)
    share = reader("attn_selected_pair_share").read({})
    assert abs(share - 23.4368) < 1e-3  # sum_i min(i + 1, 2048) at 16,384
    assert reader("attn_index_loss").read({}) == 0.07


def test_a_program_without_the_gauges_gives_none(monkeypatch):
    from torchmpi_tpu.telemetry import metrics

    real = metrics.snapshot
    monkeypatch.setattr(metrics, "snapshot", lambda *a, **kw: {
        k: v for k, v in real(*a, **kw).items() if k not in GAUGES})
    assert reader("attn_selected_pair_share").read({}) is None
    assert reader("attn_index_loss").read({}) is None


def test_the_roofline_counts_the_selected_pairs_alone():
    cfg = configs.load("keye-vl-2-30b-a3b")
    # 6 products of 2 x 32 heads x 128 over 31.46 M selected pairs a layer
    assert roofline.layer_ops(16384, 32, 128, 2048) == (
        12 * 31_458_304 * 4096)
    assert roofline.layer_bytes(16384, 32, 4, 128) == 2 * 16384 * (
        6 * 4096 + 6 * 512)
    least, bound = roofline.least_seconds(cfg, 197e12, 819e9)
    assert bound == "ops" and abs(least - 4 * 12 * 31_458_304 * 4096
                                  / 197e12) < 1e-9
    assert roofline.peak_hbm_bytes_per_s("TPU v5 lite") == 819e9
    try:
        roofline.peak_hbm_bytes_per_s("TPU v9")
    except KeyError as e:
        assert "TPU v9" in str(e)
    else:
        raise AssertionError("an unknown chip has no peak")


def test_the_roofline_reader_wants_the_kernels_and_a_selecting_config():
    read = reader("attn_sparse_kernel_roofline").read
    cfg = configs.load("keye-vl-2-30b-a3b")
    no_kernel = {"cfg": cfg, "phase": {}, "steady": {
        "steps": 20, "op_times": {"%fusion.1 = f32[8]": 1.0}}}
    assert read(no_kernel) is None
    other_model = {"cfg": configs.load("gpt2-medium"), "phase": {},
                   "steady": {"steps": 20, "op_times": {
                       "%splash_mqa_fwd_residuals.3 = (f32[4]": 1.0}}}
    assert read(other_model) is None
