"""The benchmark's pieces for the five decoder configurations,
``smallthinker-21b-a3b``, ``keye-vl-2-30b-a3b``, ``laguna-s-2-1``,
``falcon-h1-34b`` and ``brumby-14b`` (the sparse decoders of
``tests/test_moe_decoder.py``, ``tests/test_selected_attention.py`` and
``tests/test_laguna_decoder.py``, the hybrid one of
``tests/test_hybrid_decoder.py`` and the retentive one of
``tests/test_retention_decoder.py`` at the published
widths): each one's file, its operation count, its data, the
reader of its inner scopes, and its cell's whole run at the rehearsal's
sizes, sound, broken and with the fp8 control in the program's place. What
is the same for both is one test with a case for each."""

import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
CONFIG = "smallthinker-21b-a3b"
KEYE = "keye-vl-2-30b-a3b"
LAGUNA = "laguna-s-2-1"
FALCON = "falcon-h1-34b"  # no experts: a state-space mixer beside attention
BRUMBY = "brumby-14b"     # no experts, no attention: power retention
CONFIGS = [CONFIG, KEYE, LAGUNA, FALCON, BRUMBY]
UNROUTED = (FALCON, BRUMBY)
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
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_0/tm.attn.index/index_q/"
         "dot_general", "tm.attn.index"),
        (pre + "jvp(MoEDecoder)/checkpoint/MoEDecoderBlock_2/cond/"
         "branch_0_fun/tm.attn.select/tm_attn_select_kth/pallas_call",
         "tm.attn.select"),
        (pre + "transpose(jvp(MoEDecoder))/MoEDecoderBlock_1/cond/"
         "branch_0_fun/tm.attn.sparse/vmap(splash_mqa_dkv_no_residuals)/"
         "pallas_call", "tm.attn.sparse"),
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_1/tm.attn.gate/head_gate/"
         "dot_general", "tm.attn.gate"),
        (pre + "transpose(jvp(MoEDecoder))/MoEDecoderBlock_2/tm.moe.shared/"
         "shared_up/dot_general", "tm.moe.shared"),
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_0/tm.moe.dense/mlp_up/"
         "dot_general", "tm.moe.dense"),
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


@pytest.mark.parametrize("config", CONFIGS)
def test_zipf_token_ids_are_seeded_and_skewed(config):
    from benchmark import configs

    cfg = configs.load(config, rehearse=True)
    built = configs.build(config, cfg)
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


@pytest.mark.parametrize("config", CONFIGS)
def test_the_cells_rehearsal_is_correct(capsys, config):
    """The cell's whole run at the rehearsal's sizes, as
    ``benchmark/tests`` drives the other cells."""
    from benchmark import run as bench

    rc = bench.main(["--workload", config + ".stream.x1", "--seed",
                     str(2**31 + 7),
                     "--seconds", "1", "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True, out
    assert line["metrics"] == {} and line["attempted"] >= 32


@pytest.mark.parametrize("config,more", [
    (CONFIG, set()),
    (KEYE, {"attn_selected_pair_share", "attn_index_loss",
            "attn_kernel_share"}),
    (LAGUNA, {"attn_heads_held_share", "attn_kernel_share"}),
    (FALCON, {"ssm_heads_held_share", "attn_kernel_share"}),
    (BRUMBY, {"retention_heads_held_share"})], ids=CONFIGS)
def test_the_cells_traced_rehearsal_reports_the_routing_counters(
        capsys, config, more):
    """... and, for the configuration that selects its keys, what the
    selection measured; for the one held by share, the share of the heads;
    for the hybrid one, which routes nothing, the share of the mixer's
    heads; for the retentive one, which neither routes nor attends, the
    share of its KV heads (the scope metrics need a TPU's trace)."""
    from benchmark import run as bench

    rc = bench.main(["--workload", config + ".stream.x1", "--seed", "11",
                     "--trace", "1", "--rehearse"])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True, out
    routed = set() if config in UNROUTED else {
        "moe_grouped_rows_per_step", "moe_max_over_mean_load"}
    assert routed | {"engine_dispatch_ms"} | more <= set(line["rehearsed"])
    assert (config in UNROUTED) == (not [
        m for m in line["rehearsed"] if m.startswith("moe_")])
    assert (config == BRUMBY) == (not [
        m for m in line["rehearsed"] if m.startswith("attn_")])
    # its reader divides by every layer; the third's layer 0 has no experts
    assert ("moe_compact_share" in line["rehearsed"]) == (
        config in (CONFIG, KEYE))
    assert ("attn_selected_pair_share" in line["rehearsed"]) == (
        config == KEYE)
    assert ("attn_heads_held_share" in line["rehearsed"]) == (
        config == LAGUNA)


@pytest.fixture(scope="module")
def shown():
    """``benchmark/tests/test_correct.py``'s demonstrations, by path."""
    return _load(ROOT / "benchmark" / "tests" / "test_correct.py")


@pytest.mark.parametrize("config", CONFIGS)
def test_a_step_that_changes_nothing_is_not_correct_in_the_cell(
        shown, capsys, monkeypatch, config):
    shown.test_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch, config + ".stream.x1")


@pytest.mark.parametrize("config", CONFIGS)
def test_the_fp8_control_is_not_correct_in_the_cell(shown, config):
    shown.test_fp8_control_is_not_correct(config + ".stream.x1")


# -- the configuration held by share of heads, experts and columns ---------
def test_flops_of_the_shared_configuration_are_the_issues_arithmetic():
    from benchmark import configs, decoder_flops, gated_decoder_flops

    assert decoder_flops.visible_pairs(16384, 512) == 8_257_792
    built = configs.load_module(
        ROOT / "benchmark" / "configs" / f"{LAGUNA}.py")
    cfg = configs.load(LAGUNA)
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
        (ROOT / "benchmark" / "configs" / f"{LAGUNA}.json").read_text())
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
    entry = next(c for c in spec["configs"] if c["name"] == LAGUNA)
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

    cfg = configs.load(LAGUNA, rehearse=True)
    mod = configs.load_module(ROOT / "benchmark" / "configs" / f"{LAGUNA}.py")
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


# -- the configuration that selects its keys -------------------------------
def test_flops_of_the_selecting_configuration_are_the_issues_arithmetic():
    from benchmark import configs, decoder_flops, selected_decoder_flops

    assert selected_decoder_flops.selected_pairs(16384, 2048) == 31_458_304
    assert decoder_flops.visible_pairs(16384) == 134_225_920
    forward = selected_decoder_flops.selected_decoder_forward_flops(
        16384, 2048, 32, 4, 128, 768, 128, 8, 16, 18992, 4, 16, 64, 2048)
    assert 7.85e12 < forward < 7.87e12         # ISSUE 30: 7.86 T forward
    built = configs.build(KEYE, configs.load(KEYE))
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
        (ROOT / "benchmark" / "configs" / f"{KEYE}.json").read_text())
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
    entry = next(c for c in spec["configs"] if c["name"] == KEYE)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    tiny = cfg["rehearsal"]
    assert tiny["sa_config"]["topk"] < tiny["sequence_length"]
    assert tiny["num_key_value_heads"] == 2


def broken_run(capsys):
    from benchmark import run as bench

    rc = bench.main(["--workload", KEYE + ".stream.x1", "--seed", "41",
                     "--seconds", "1", "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    return rc, json.loads(out.strip().splitlines()[-1]), out


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
