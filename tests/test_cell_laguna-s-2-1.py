"""``laguna-s-2-1.stream.x1``, the configuration held by share of heads,
experts and columns, at its rehearsal's sizes: the cases every decoder
configuration's cell has (``decoder_cases.py``), run here for this one, then
what is this configuration's alone: its operation count, its file's
published widths, and the experts it holds. Its traced rehearsal reports the
share of the heads held; not ``moe_compact_share``, whose reader divides by
every layer where this configuration's layer 0 has no experts. (More is in
``tests/test_laguna_decoder.py``.)"""

import json

import numpy as np
import pytest

from decoder_cases import (  # noqa: F401 - collected here, for CONFIG
    ROOT,
    test_a_step_that_changes_nothing_is_not_correct_in_the_cell,
    test_the_cells_rehearsal_is_correct,
    test_the_cells_traced_rehearsal_reports_the_routing_counters,
    test_the_fp8_control_is_not_correct_in_the_cell,
    test_zipf_token_ids_are_seeded_and_skewed,
)

CONFIG = "laguna-s-2-1"
MORE = {"moe_grouped_rows_per_step", "moe_max_over_mean_load",
        "attn_heads_held_share", "attn_kernel_share"}
ABSENT = ("moe_compact_share", "attn_selected_pair_share")


def test_flops_of_the_shared_configuration_are_the_issues_arithmetic():
    from benchmark import configs, decoder_flops, gated_decoder_flops

    assert decoder_flops.visible_pairs(16384, 512) == 8_257_792
    built = configs.load_module(
        ROOT / "benchmark" / "configs" / f"{CONFIG}.py")
    cfg = configs.load(CONFIG)
    windows = built.windows_of(cfg)
    assert windows == [None, 512, 512, 512, None]
    count = lambda **over: (  # noqa: E731
        gated_decoder_flops.gated_decoder_forward_flops(**{**dict(
            seq=16384, d_model=3072, heads=[6, 9, 9, 9, 6], kv_heads=1,
            head_dim=128, windows=windows, dense_layers=1,
            dense_columns=1536, expert_width=1024, shared_width=1024,
            experts=256, top_k=10, held=8, vocab=12544), **over}))
    forward = count()
    assert 5.52e12 < forward < 5.54e12          # ISSUE 32: 5.53 T forward
    assert built.build(cfg).flops_per_sample == 3 * forward  # 16.59 T
    t, d = 16384, 3072
    # each part by itself: the head 23 %, the shared experts 22 %, ...
    assert forward - count(vocab=0) == 2 * t * d * 12544
    assert forward - count(shared_width=0) == 4 * t * 6 * d * 1024
    assert forward - count(dense_columns=0) == t * 6 * d * 1536
    assert forward - count(held=0) == 4 * (t * 10 * 8 * 6 * d * 1024 // 256)
    # a query head more in layer 1: its columns of q and o, its gate, and
    # its scores and values over the window's pairs
    assert count(heads=[6, 10, 9, 9, 6]) - forward == (
        2 * t * d * (2 * 128 + 1) + 4 * 8_257_792 * 128)


def test_the_shared_configurations_file_keeps_the_published_widths():
    """Every number of the catalog's entry under its own key, but those
    that are cut, which ``reduced`` and ``published`` name: counts of
    layers, experts, rows and heads, never a width."""
    cfg = json.loads(
        (ROOT / "benchmark" / "configs" / f"{CONFIG}.json").read_text())
    catalog = {
        "model_type": "laguna", "hidden_size": 3072,
        "intermediate_size": 12288, "head_dim": 128,
        "max_position_embeddings": 1048576, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512, "moe_apply_router_weight_on_input": False,
        "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 8192, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 10000,
                "partial_rotary_factor": 1}},
    }
    for key, value in catalog.items():
        assert cfg[key] == value and key not in cfg["reduced"], key
    cut = {
        "num_hidden_layers": (5, 48), "num_experts": (8, 256),
        "vocab_size": (12544, 100352), "num_attention_heads": (6, 48),
        "num_key_value_heads": (1, 8),
        "num_attention_heads_per_layer": ([6, 9, 9, 9, 6],
                                          [48, 72, 72, 72]),
        "layer_types": (["full_attention"] + ["sliding_attention"] * 3
                        + ["full_attention"], None),
        "mlp_layer_types": (["dense"] + ["sparse"] * 4, None),
        "gating_types": (["per_head"] * 5, None)}
    assert sorted(cut) == sorted(cfg["reduced"])
    for key, (here, published) in cut.items():
        assert cfg[key] == here and key in cfg["published"]
        if published is not None:
            assert cfg["published"][key] == published
    # no width among the cuts: sizes, dims and the experts a token stay
    assert not [k for k in cfg["reduced"] if k.endswith(
        ("_size", "_dim", "_rank", "_tok", "_factor"))
        and k != "vocab_size"]
    assert cfg["model"] == {**cfg["model"], "router_outputs": 256,
                            "experts_held": list(range(8)),
                            "dense_columns_held": 1536}
    assert cfg["vocab_size"] * 8 == 100352
    assert cfg["model"]["dense_columns_held"] * 8 == cfg["intermediate_size"]
    assert cfg["sequence_length"] == 16384 and cfg["per_chip_batch"] == 1
    assert "32 chips" in cfg["deployment"] and "KV head 0" in cfg[
        "deployment"]
    assert {"auxiliary_loss"} == set(cfg["departures"])
    assert {"router", "activation", "projections", "rotary", "head_gate",
            "intermediate_size"} <= set(cfg["assumed"])
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    tiny = cfg["rehearsal"]
    assert tiny["sliding_window"] < tiny["sequence_length"]
    assert tiny["num_attention_heads_per_layer"] == [2, 3, 3, 3, 2]
    assert tiny["model"]["experts_held"] == [0, 1]


@pytest.mark.parametrize("seed", [3, 77, 2**31 + 5])
def test_the_held_experts_are_the_group_at_the_mean_expected_load(seed):
    """Each router's seeded columns are turned by whole groups so that the
    experts held here are the group whose expected load is nearest the mean
    share; the expectation worked again here in plain numpy (each id
    through the feed-forward sublayers by itself, an expert expected an
    id's tokens by its logit's distance from the middle between the id's
    third and fourth), and the turn undone and found again."""
    import jax

    from benchmark import configs

    cfg = configs.load(CONFIG, rehearse=True)
    mod = configs.load_module(ROOT / "benchmark" / "configs" / f"{CONFIG}.py")
    params = jax.device_get(mod.build(cfg).make_state(seed)[0])
    n = 2
    zipf = 1.0 / np.arange(1, 98)
    count = 2 * 56 * zipf / zipf.sum()
    silu = lambda a: a / (1.0 + np.exp(-a))  # noqa: E731
    h = params["embed"]["embedding"].astype(np.float64)
    for i in range(5):
        block = params[f"MoEDecoderBlock_{i}"]
        x = h / np.sqrt((h * h).mean(-1, keepdims=True) + 1e-6) * block[
            "norm_moe"]["scale"]
        part = "shared" if i else "mlp"
        if i:
            logits = x @ block["router"]["kernel"]
            edge = np.sort(logits, axis=-1)[:, -4:-2].mean(-1, keepdims=True)
            load = (count[:, None] * 1.0 / (1.0 + np.exp(
                -(logits - edge) / mod.SOFT))).sum(0).reshape(-1, n).sum(-1)
            off = np.abs(load - load.mean())
            assert off[0] == off.min(), (i, off)
        h = h + (silu(x @ block[part + "_gate"]["kernel"])
                 * (x @ block[part + "_up"]["kernel"])
                 ) @ block[part + "_down"]["kernel"]
    # a router turned further by one group is turned back, nothing else moves
    again = mod.held_at_mean_load(cfg, params)
    moved = {**params, "MoEDecoderBlock_2": {
        **params["MoEDecoderBlock_2"], "router": {"kernel": np.roll(
            params["MoEDecoderBlock_2"]["router"]["kernel"], n, axis=1)}}}
    back = mod.held_at_mean_load(cfg, moved)
    for tree in (again, back):
        assert jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda a, b: bool(np.array_equal(a, b)), params, tree))
