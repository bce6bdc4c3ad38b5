"""``qwen3-next-80b-a3b.stream.x1``'s training step at its real size for the
described chip: the cases every decoder configuration's step has
(``decoder_cases.py``), run here for this one on one lowering and one
compilation, then what only a gated-delta step can hold or leave out."""

import math
import re

from decoder_cases import (  # noqa: F401 - collected here, for CONFIG
    benchmark_spec,
    cell_of,
    compiled,
    kernel_phases,
    lowered,
    one_chip,
    per_layer_of,
    test_the_cells_step_fits_the_chip,
    test_the_cells_step_keeps_the_products_the_rule_counted,
    test_the_cells_step_lowers_for_the_chip_to_the_text_it_had,
    test_the_configuration_is_a_cell_of_the_benchmark,
    two_tiers,
    whole_logits,
)

CONFIG = "qwen3-next-80b-a3b"
# as PR 45 brought it 1,617,018 a72bda11e988512e; since PR 46 a linear
# layer's convolution and its SiLU are one operation with a derivative rule
# of its own (``parallel/ssm.py`` ``causal_conv1d_silu``), two kernels where
# a pad, four slices and a SiLU stood, forward and differentiated by jax
# since PR 47 the model traces its two kinds of block once before the step
# (``models.lm.products_kept``) and keeps none of their products here: the
# same operations line for line, the numbers in the private functions' names
# (``@_where_50`` -> ``@_where_51``) moved, 2,326 lines of 1fc29d592acdde1e's
PIN = (1575977, "09af15f9c8ccb081")
OWN = ["conv_kernel_share", "gdn_chunk_ms_per_step", "gdn_chunks_per_step",
       "gdn_conv_ms_per_step", "gdn_gate_ms_per_step", "gdn_peak_share",
       "gdn_proj_ms_per_step", "gdn_state_ms_per_step"]
# 3 linear layers of 88,250,560 (the mixer 33,718,464), the full layer's
# 81,795,584 (its mixer 27,263,488), 77,791,232 of vocabulary and the last
# norm (ISSUE 45: 424.3 M)
PARAMETERS = (3 * 88_250_560 + 81_795_584 + 2 * 18992 * 2048 + 2048,) * 2
# 12 B a parameter of state (4.74 GiB) and 8.51 GiB of temporaries measured
# here, 13.26 GiB, at 1 x 16,384 (not the fallback of 8,192) with the
# per-chunk states kept, under the 15.0 GiB ISSUE 45 set; the limit is what
# was measured and a margin. (14.54 GiB and 71 instructions of XLA's own
# rematerialization with the inverse as a product whose powers jax kept for
# backward; 14.00 and none once the convolution was made again in backward;
# 13.13 with the normalised heads made again with it; 13.26 with the
# triangular solve the chip ran faster.)
FITS_IN = 13.5 * 2**30
# the temporaries of the step with no product kept: this step, for the
# rule's estimate of it is 14.5 GiB, over the 14.0 it may fill, and it
# keeps none of the seven kinds the file names (PR 47; with the router's
# logits and the residual kept, 0.375 GiB, the compiler's buffers came to
# 11.11 GiB, 2 GiB LESS than with nothing kept: PERF.md section 7)
NOTHING_KEPT = 9_005_765_120
PRODUCTS = (230, 230)
# the one full layer's attention takes the fused kernels at heads of 256, 8
# query heads to each of the 2 KV heads under tiles of 1,024: one forward
# and one backward, the recomputed block keeps what forward made
ATTENTION_KERNELS = {"splash_mqa_fwd_residuals", "splash_mqa_dkv_no_residuals"}
# ... and a linear layer's convolution with its SiLU is one kernel forward,
# once more in the block's recomputation and once more in its own checkpoint
# (``models/deltanet.py``: for the L2 norms behind it), and one backward
KERNELS = {**dict.fromkeys(ATTENTION_KERNELS, 1),
           "tm_conv_silu_fwd": 9, "tm_conv_silu_bwd": 3}
HOLDS = ("ragged-dot",)
# not an instruction of XLA's own rematerialization
HOLDS_NO = (r"\.remat",)


def test_the_gated_delta_cells_step_holds_its_state_by_chunks(compiled):
    """No state a position (``[16384, 32, 128, 128]`` in any grouping of the
    heads), kept or transient, forward or backward: the widest array with a
    state's ``[128, 128]`` for its last axes is the chunks' states, 512 MiB
    a layer while that layer's backward runs; the triangular systems are
    ``[64, 64]`` a chunk and value head; one loop over the chunks a linear
    layer, forward, recomputed and backward; no array holds the logits of
    all 16,384 rows (``whole_logits``); the four expert layers have their
    two tiers, forward and backward."""
    from torchmpi_tpu.parallel import ep

    cfg, text = compiled.cfg, compiled.text
    assert not whole_logits(text, cfg)
    seq, chunk = cfg["sequence_length"], cfg["model"]["gdn_chunk"]
    heads = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    assert (seq, chunk, heads, dk, dv) == (16384, 64, 32, 128, 128)
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text)}
    states = [s for s in shapes if s[-2:] == (dk, dv)
              and math.prod(s) >= heads * dk * dv]
    assert (seq // chunk, 1, 16, 2, dk, dv) in states
    assert max(math.prod(s) for s in states) == seq // chunk * heads * dk * dv
    assert not [s for s in shapes if math.prod(s) >= seq * heads * dk * dv]
    assert (seq // chunk, 16, 2, chunk, chunk) in shapes
    linear = sum((i + 1) % cfg["full_attention_interval"] != 0
                 for i in range(cfg["num_hidden_layers"]))
    # ... and one over the head's blocks of rows
    assert text.count(" while(") == 3 * linear + 1
    routes = seq * cfg["num_experts_per_tok"]
    assert ep.compact_rows(routes, 16, 512) == 10240
    assert two_tiers(text, routes, (
        cfg["hidden_size"], cfg["moe_intermediate_size"])) == 2 * cfg[
            "num_hidden_layers"]


def test_the_gated_delta_cells_convolution_pads_nothing(compiled):
    """A linear layer's convolution shifts its rows in VMEM
    (``ops/conv_kernel.py``): no array of the sequence and the taps' reach
    before it (``[1, 16387, 8192]``: the padded float32 copy of XLA's
    lowering, 512 MiB, and its gradient) is in the compiled step, forward,
    recomputed or backward; and the kernels' calls bear the scope the
    expressions stood under, so ``gdn_conv_ms_per_step`` reads them: a
    layer's forward, the block's recomputation with the mixer's own, and
    backward."""
    cfg, text = compiled.cfg, compiled.text
    reach = cfg["sequence_length"] + cfg["linear_conv_kernel_dim"] - 1
    assert reach == 16387 and not re.search(r"\[(1,)?%d," % reach, text)
    scope = "tm.lm.gdn_conv"
    assert kernel_phases(text, "tm_conv_silu") == {
        (scope, "forward"): 3, (scope, "recompute"): 6, (scope, "backward"): 3}


def test_the_gated_delta_cell_reads_what_the_shared_one_reads_but_the_window():
    """... and the dense layer's and the share of heads: its softmax layer is
    full, every layer has experts and every head is held; what it reads
    beyond is its own."""
    spec = benchmark_spec()
    third = per_layer_of(spec, cell_of("laguna-s-2-1"))
    eighth = per_layer_of(spec, cell_of(CONFIG))
    assert third - eighth == {
        "attn_window_ms_per_step", "mlp_dense_ms_per_step",
        "attn_heads_held_share"}
    # every layer has experts, so the share of them that took the compact
    # tier is read here, as in the two cells whose every layer has; the
    # convolution's share of kernels it reads with the hybrid cell
    assert eighth - third == {"moe_compact_share", "conv_kernel_share"} | {
        m["name"] for m in spec["per_layer"]
        if m["workloads"] == [cell_of(CONFIG)]}
    assert {m["layer"] for m in spec["per_layer"]
            if m["name"].startswith("gdn_")} == {"gated delta rule"}
