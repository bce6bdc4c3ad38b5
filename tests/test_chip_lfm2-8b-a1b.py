"""``lfm2-8b-a1b.stream.x1``'s training step at its real size for the
described chip: the cases every decoder configuration's step has
(``decoder_cases.py``), run here for this one on one lowering and one
compilation, then what only a step of gated short convolutions under a
biased router and a tied head can hold or leave out."""

from decoder_cases import (  # noqa: F401 - collected here, for CONFIG
    benchmark_spec,
    cell_of,
    compiled,
    lowered,
    one_chip,
    per_layer_of,
    row_scatters,
    test_the_cells_step_fits_the_chip,
    test_the_cells_step_keeps_the_products_the_rule_counted,
    test_the_cells_step_lowers_for_the_chip_to_the_text_it_had,
    test_the_configuration_is_a_cell_of_the_benchmark,
    two_tiers,
    whole_logits,
)

CONFIG = "lfm2-8b-a1b"
# as PR 48 brought it
PIN = (918571, "617c007bcea79b5f")
OWN = ["moe_bias_max_abs", "moe_biased_route_share", "short_conv_hbm_share",
       "short_conv_ms_per_step", "short_conv_proj_ms_per_step"]
# the leading layer 60,827,648 (its mixer 16,783,360: W_in 12,582,912, W_out
# 4,194,304, the taps 6,144), the attention layer 98,635,904 (its mixer
# 10,485,888), three convolution layers of 104,933,376, the tied table's
# 33,554,432 rows x width and the last norm (ISSUE 48: 507.8 M)
PARAMETERS = (60_827_648 + 98_635_904 + 3 * 104_933_376 + 16384 * 2048
              + 2048,) * 2
# 12 B a parameter of state (5.68 GiB) and 4.05 GiB of temporaries measured
# here, 9.73 GiB, at 2 x 8,192 with every kind of named product kept
FITS_IN = 10.25 * 2**30
# the temporaries of the step with no product kept
# (``scripts/recompute_probe.py lfm2-8b-a1b --keep none --compile``)
NOTHING_KEPT = 3_300_686_848
PRODUCTS = (60, 78)
# the one attention layer takes the fused kernels at heads of 64, 4 query
# heads to each of the 8 KV heads, under tiles of 1,024: one forward and one
# backward, the recomputed block keeps what forward made. The convolution
# has no kernel
KERNELS = {"splash_mqa_fwd_residuals": 1, "splash_mqa_dkv_no_residuals": 1}
ATTENTION_KERNELS = set(KERNELS)
HOLDS = ("ragged-dot",)
# not an instruction of XLA's own rematerialization, nor a kernel of the
# convolution with a SiLU this mixer has not
HOLDS_NO = (r"\.remat", "tm_conv_silu")


def test_the_tied_cells_step_has_one_table_and_its_two_tiers(
        lowered, compiled):
    """The parameters hold no head: the table of 16,384 rows is the one leaf
    of the vocabulary, and no array holds the logits of all 16,384 token
    rows (``whole_logits``), nor is the table's gradient a scatter of rows;
    the four expert layers have their two tiers, forward and backward, a
    compact one of 32,768 of 65,536 routes' rows; one loop, over the head's
    blocks of rows."""
    from torchmpi_tpu.parallel import ep

    cfg, params = lowered[0], lowered[1]
    assert "head" not in params
    assert params["embed"]["embedding"].shape == (16384, 2048)
    assert {"in_proj", "conv_kernel", "out_proj"} <= set(
        params["MoEDecoderBlock_0"])
    assert {"q", "k", "v", "o", "q_norm", "k_norm"} <= set(
        params["MoEDecoderBlock_1"])
    text = compiled.text
    assert not whole_logits(text, cfg) and not row_scatters(text, cfg)
    assert text.count(" while(") == 1
    routes = cfg["per_chip_batch"] * cfg["sequence_length"] * cfg[
        "num_experts_per_tok"]
    assert ep.compact_rows(routes, 8, 32) == 32768
    assert two_tiers(text, routes, (
        cfg["hidden_size"], cfg["moe_intermediate_size"])) == 2 * (
            cfg["num_hidden_layers"] - cfg["num_dense_layers"])


def test_the_cell_reads_what_the_shared_one_reads_but_its_gates():
    """... and the window and the share of heads: its one attention layer is
    full and whole; what it reads beyond is the convolution's, the bias's,
    and the share of layers that took the compact tier (80 at best: the
    reader divides by every layer, and the leading one has no experts)."""
    spec = benchmark_spec()
    third = per_layer_of(spec, cell_of("laguna-s-2-1"))
    ninth = per_layer_of(spec, cell_of(CONFIG))
    assert third - ninth == {
        "attn_window_ms_per_step", "attn_gate_ms_per_step",
        "moe_shared_ms_per_step", "attn_heads_held_share"}
    assert ninth - third == {"moe_compact_share", "conv_kernel_share", *OWN}
    assert {m["layer"] for m in spec["per_layer"]
            if m["name"].startswith("short_conv_")} == {
                "gated short convolution"}
    assert len(spec["workloads"]) == 10
    assert [w["name"] for w in spec["workloads"] if w["chips"] == 4] == [
        "gpt2-medium.stream.x4"]
