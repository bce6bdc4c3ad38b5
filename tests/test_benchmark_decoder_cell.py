"""The benchmark's pieces for the configuration ``smallthinker-21b-a3b``
(the sparse decoder of ``tests/test_moe_decoder.py`` at the published
widths): its file, its operation count, its data, the reader of its inner
scopes, and its cell's whole run at the rehearsal's sizes, sound, broken
and with the fp8 control in the program's place."""

import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
CONFIG = "smallthinker-21b-a3b"
CELL = CONFIG + ".stream.x1"


def _load(path):
    spec = importlib.util.spec_from_file_location(
        "plain_" + path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_inner_scope_reader_sees_through_wrappers():
    from benchmark import inner_scopes

    pre = "jit(tm_step)/shard_map/tm.fwd_bwd/"
    for op, want in [
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_0/tm.attn.full/while/body/"
         "dot_general", "tm.attn.full"),
        (pre + "transpose(jvp(MoEDecoder))/tm.fwd_bwd/jvp(MoEDecoder)/"
         "checkpoint/rematted_computation/MoEDecoderBlock_1/tm.attn.window/"
         "while/body/exp", "tm.attn.window"),
        (pre + "transpose(jvp(MoEDecoder/MoEDecoderBlock_2/tm.moe.experts))"
         "/ragged_dot", "tm.moe.experts"),
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_3/tm.moe.route/sort",
         "tm.moe.route"),
        (pre + "jvp(MoEDecoder)/tm.moe.combine/reduce_sum",
         "tm.moe.combine"),
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_3/q/dot_general", None),
        ("jit(tm_step)/shard_map/tm.optimizer/mul", None), ("", None),
    ]:
        assert inner_scopes.inner_scope_of(op) == want, op


@pytest.mark.parametrize("seq,window", [
    (16, None), (16, 5), (16, 16), (16, 40), (9, 1), (64, 24)])
def test_visible_pairs_counts_the_band_exactly(seq, window):
    from benchmark import decoder_flops

    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    assert decoder_flops.visible_pairs(seq, window) == int(seen.sum())


def test_flops_of_the_configuration_are_the_issues_arithmetic():
    from benchmark import configs, decoder_flops

    cfg = configs.load(CONFIG)
    built = configs.load_module(
        ROOT / "benchmark" / "configs" / f"{CONFIG}.py")
    forward = decoder_flops.moe_decoder_forward_flops(
        8192, 2560, 28, 4, 128, 768, 64, 6, 8, 18992, built.windows_of(cfg))
    per_token = forward / 8192
    assert 492e6 < per_token < 493e6          # 395 + 97 MFLOP forward
    assert 12.0e12 < 3 * forward < 12.2e12    # a sequence trained
    # the experts are the nominal share: 6 x 8/64 of an expert a token
    fewer = decoder_flops.moe_decoder_forward_flops(
        8192, 2560, 28, 4, 128, 768, 64, 6, 4, 18992, built.windows_of(cfg))
    assert forward - fewer == 4 * 8192 * 6 * 4 * 3 * 2 * 2560 * 768 // 64


def test_configuration_file_keeps_the_published_widths():
    cfg = json.loads(
        (ROOT / "benchmark" / "configs" / f"{CONFIG}.json").read_text())
    catalog = {
        "head_dim": 128, "hidden_size": 2560, "moe_ffn_hidden_size": 768,
        "max_position_embeddings": 16384,
        "moe_num_active_primary_experts": 6, "num_attention_heads": 28,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_theta": 1500000, "sliding_window_size": 4096,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "tie_word_embeddings": False, "rope_scaling": None,
        "model_name": "smallthinker_21b_instruct",
    }
    for key, value in catalog.items():
        assert cfg[key] == value and key not in cfg["reduced"], key
    cut = {"num_hidden_layers": (4, 52), "moe_num_primary_experts": (8, 64),
           "vocab_size": (18992, 151936)}
    for key, (here, published) in cut.items():
        assert cfg[key] == here and key in cfg["reduced"]
        assert cfg["published"][key] == published
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] == [0, 1, 1, 1]
    assert cfg["model"]["router_outputs"] == 64
    assert cfg["vocab_size"] * 8 == 151936
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    tiny = cfg["rehearsal"]
    assert tiny["sliding_window_size"] < tiny["sequence_length"]
    assert tiny["num_key_value_heads"] == 2
    assert tiny["model"]["experts_held"] == [0, 1]
    assert tiny["model"]["router_outputs"] == 8


def test_zipf_token_ids_are_seeded_and_skewed():
    from benchmark import configs

    cfg = configs.load(CONFIG, rehearse=True)
    built = configs.build(CONFIG, cfg)
    big = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    x, y = built.make_data(big, 64)
    x2, _ = built.make_data(big, 64)
    np.testing.assert_array_equal(x, x2)
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])
    assert x.dtype == np.int32 and x.min() >= 0 and x.max() < 97
    counts = np.bincount(x.ravel(), minlength=97)
    # p(id) is 1 / (id + 1) over H_97 = 5.15: id 0 near a fifth
    assert 0.15 < counts[0] / x.size < 0.24
    assert counts[0] > counts[1] > counts[3] > counts[9] > counts[40]


def test_the_cells_rehearsal_is_correct(capsys):
    """The new cell's whole run at the rehearsal's sizes, as
    ``benchmark/tests`` drives the other cells."""
    from benchmark import run as bench

    rc = bench.main(["--workload", CELL, "--seed", str(2**31 + 7),
                     "--seconds", "1", "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True, out
    assert line["metrics"] == {} and line["attempted"] >= 32


def test_the_cells_traced_rehearsal_reports_the_routing_counters(capsys):
    from benchmark import run as bench

    rc = bench.main(["--workload", CELL, "--seed", "11", "--trace", "1",
                     "--rehearse"])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True, out
    assert {"moe_grouped_rows_per_step", "moe_max_over_mean_load",
            "moe_compact_share", "engine_dispatch_ms"} <= set(
                line["rehearsed"])


@pytest.fixture(scope="module")
def shown():
    """``benchmark/tests/test_correct.py``'s demonstrations, by path."""
    return _load(ROOT / "benchmark" / "tests" / "test_correct.py")


def test_a_step_that_changes_nothing_is_not_correct_in_the_cell(
        shown, capsys, monkeypatch):
    shown.test_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch, CELL)


def test_the_fp8_control_is_not_correct_in_the_cell(shown):
    shown.test_fp8_control_is_not_correct(CELL)
