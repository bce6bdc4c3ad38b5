"""``smallthinker-21b-a3b.stream.x1`` at its rehearsal's sizes: the cases
every decoder configuration's cell has (``decoder_cases.py``), run here for
this one, then what is this configuration's alone: its operation count and
its file's published widths. Its every layer routes, and its traced
rehearsal reports the expert layer's counters and the attention kernels'
share. (More is in ``tests/test_moe_decoder.py``.)"""

import json

from decoder_cases import (  # noqa: F401 - collected here, for CONFIG
    ROOT,
    test_a_step_that_changes_nothing_is_not_correct_in_the_cell,
    test_the_cells_rehearsal_is_correct,
    test_the_cells_traced_rehearsal_reports_the_routing_counters,
    test_the_fp8_control_is_not_correct_in_the_cell,
    test_zipf_token_ids_are_seeded_and_skewed,
)

CONFIG = "smallthinker-21b-a3b"
MORE = {"moe_grouped_rows_per_step", "moe_max_over_mean_load",
        "moe_compact_share", "attn_kernel_share"}
ABSENT = ("attn_selected_pair_share", "attn_heads_held_share")


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
