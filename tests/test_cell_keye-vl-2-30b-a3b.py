"""``keye-vl-2-30b-a3b.stream.x1``, the configuration that selects its keys,
at its rehearsal's sizes: the cases every decoder configuration's cell has
(``decoder_cases.py``), run here for this one, then what is this
configuration's alone: its operation count, its file's published widths, and
two ways its selection can be wrong that the comparison must see. Its traced
rehearsal reports what the selection measured beside the expert layer's
counters. (More is in ``tests/test_selected_decoder.py``.)"""

import json

from decoder_cases import (  # noqa: F401 - collected here, for CONFIG
    ROOT,
    rehearsed_run,
    test_a_step_that_changes_nothing_is_not_correct_in_the_cell,
    test_the_cells_rehearsal_is_correct,
    test_the_cells_traced_rehearsal_reports_the_routing_counters,
    test_the_fp8_control_is_not_correct_in_the_cell,
    test_zipf_token_ids_are_seeded_and_skewed,
)

CONFIG = "keye-vl-2-30b-a3b"
MORE = {"moe_grouped_rows_per_step", "moe_max_over_mean_load",
        "moe_compact_share", "attn_selected_pair_share", "attn_index_loss",
        "attn_kernel_share"}
ABSENT = ("attn_heads_held_share",)


def test_flops_of_the_selecting_configuration_are_the_issues_arithmetic():
    from benchmark import configs, decoder_flops, selected_decoder_flops

    assert selected_decoder_flops.selected_pairs(16384, 2048) == 31_458_304
    assert decoder_flops.visible_pairs(16384) == 134_225_920
    forward = selected_decoder_flops.selected_decoder_forward_flops(
        16384, 2048, 32, 4, 128, 768, 128, 8, 16, 18992, 4, 16, 64, 2048)
    assert 7.85e12 < forward < 7.87e12         # ISSUE 30: 7.86 T forward
    built = configs.build(CONFIG, configs.load(CONFIG))
    assert built.flops_per_sample == 3 * forward  # 23.6 T a training step
    # attention is counted over the selected pairs, the indexer over all
    fewer = selected_decoder_flops.selected_decoder_forward_flops(
        16384, 2048, 32, 4, 128, 768, 128, 8, 16, 18992, 4, 16, 64, 1024)
    assert forward - fewer == 4 * 4 * 32 * 128 * (
        31_458_304 - selected_decoder_flops.selected_pairs(16384, 1024))


def test_the_selecting_configurations_file_keeps_the_published_widths():
    """Every number of the catalog's entry under its own key, but the four
    that are cut, which ``reduced`` and ``published`` name."""
    cfg = json.loads(
        (ROOT / "benchmark" / "configs" / f"{CONFIG}.json").read_text())
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "KeyeVL2", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_theta": 10000000,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False,
    }
    for key, value in catalog.items():
        assert cfg[key] == value and key not in cfg["reduced"], key
    cut = {"num_hidden_layers": (4, 48), "num_experts": (16, 128),
           "num_local_experts": (16, 128), "vocab_size": (18992, 151936)}
    assert sorted(cut) == sorted(cfg["reduced"])
    for key, (here, published) in cut.items():
        assert cfg[key] == here and cfg["published"][key] == published
    assert cfg["model"]["router_outputs"] == 128
    assert cfg["model"]["experts_held"] == list(range(16))
    assert cfg["vocab_size"] * 8 == 151936
    assert cfg["sequence_length"] == 16384 and cfg["per_chip_batch"] == 1
    assert "8 chips" in cfg["deployment"] and "precision highest" in cfg[
        "indexer_precision"]
    assert {"vision_tower", "auxiliary_loss", "indexer_bits"} == set(
        cfg["departures"])
    assert {"rotary", "qk_norm", "indexer", "selection",
            "indexer_loss"} <= set(cfg["assumed"])
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    tiny = cfg["rehearsal"]
    assert tiny["sa_config"]["topk"] < tiny["sequence_length"]
    assert tiny["num_key_value_heads"] == 2


def broken_run(capsys):
    return rehearsed_run(
        capsys, CONFIG, "--seed", "41", "--seconds", "1", "--trace", "0")


def test_one_key_too_few_selected_is_not_correct(capsys, monkeypatch):
    """The program selecting ``topk - 1`` keys a query where the model
    states ``topk``: the reference selects ``topk``, and the comparison
    says so."""
    from torchmpi_tpu.parallel import selected_attention as sa

    real = sa._threshold_rows
    monkeypatch.setattr(
        sa, "_threshold_rows",
        lambda scores, rows, top_k: real(scores, rows, top_k - 1))
    rc, line, out = broken_run(capsys)
    assert rc == 0 and line["correct"] is False, out
    assert "OUTSIDE" in out


def test_an_indexer_fed_bfloat16_is_not_correct(capsys, monkeypatch):
    """The indexer reading its input rounded to bfloat16: its scores move
    in the third digit, other keys are selected than the reference's, and
    the comparison says so."""
    import jax.numpy as jnp

    from torchmpi_tpu.models.decoder import MoEDecoderBlock

    real = MoEDecoderBlock._indexer
    monkeypatch.setattr(
        MoEDecoderBlock, "_indexer",
        lambda self, h: real(self, h.astype(jnp.bfloat16)))
    rc, line, out = broken_run(capsys)
    assert rc == 0 and line["correct"] is False, out
    assert "OUTSIDE" in out
