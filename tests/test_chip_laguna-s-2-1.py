"""``laguna-s-2-1.stream.x1``'s training step at its real size for the
described chip: the cases every decoder configuration's step has
(``decoder_cases.py``), run here for this one on one lowering and one
compilation, then what only a step held by share can hold or leave out."""

from decoder_cases import (  # noqa: F401 - collected here, for CONFIG
    benchmark_spec,
    cell_of,
    compiled,
    lowered,
    one_chip,
    per_layer_of,
    test_the_cells_step_fits_the_chip,
    test_the_cells_step_keeps_the_products_the_rule_counted,
    test_the_cells_step_lowers_for_the_chip_to_the_text_it_had,
    test_the_configuration_is_a_cell_of_the_benchmark,
    two_tiers,
)

CONFIG = "laguna-s-2-1"
# as PR 40 lowered it: a recomputed block keeps the fused kernels' output and
# log-sum-exp and holds no second forward kernel (1,492,140 fc51f3fc3cfee276
# before; the text is longer because the backward kernel's tile tables,
# constants, now stand in forward's barrier too); PR 42's rule of the token
# lookup (``embedding.takes_sorted_sum``) keeps jax's transpose at this
# table's width, and the step was the parent's text letter for letter; since
# PR 43 the head and its loss are one function with a derivative rule of its
# own (``models/lm_head.py``), a loop over blocks of 8,192 rows where the
# float32 logits of every row stood (2,943,293 162f73ace4dfee71 before)
# since PR 47 a block's backward reads the results of the products the file
# names, every kind of them kept by ``models.lm``'s rule, and makes none of
# them again (2,945,541 df2d658f547a19f3 before)
PIN = (2942474, "6f336c7eb11b63b6")
OWN = ["attn_gate_ms_per_step", "attn_heads_held_share",
       "mlp_dense_ms_per_step", "moe_shared_ms_per_step"]
PARAMETERS = (468.8e6, 469.0e6)  # 19.7 + 3 x 93.6 + 91.3 + 77.1 M
# 12 B a parameter of state and 5.04 GiB of temporaries measured here
# (10.29 GiB; 10.16 before the five layers' attention outputs and
# log-sum-exps were kept), at 1 x 16,384 (not the fallback of 8,192)
# ... and 10.93 GiB since the blocks keep every kind the file names (PR 47:
# the router's logits, ``q``, ``k``, ``v``, the head gate's columns, the
# residual after ``o``, the shared expert's and the dense layer's gate and
# up: 1.086 GiB counted, the temporaries 4.833 -> 5.693 GiB)
FITS_IN = 11.5 * 2**30
# the temporaries of the step with no product kept
# (``scripts/recompute_probe.py laguna-s-2-1 --keep none --compile``)
NOTHING_KEPT = 5_189_658_624
PRODUCTS = (135, 174)
# every layer's attention takes the fused kernels with 6 or 9 query heads to
# the one KV head and a window of 512 under tiles of 1,024: one forward and
# one backward a layer
KERNELS = {"splash_mqa_fwd_residuals": 5, "splash_mqa_dkv_no_residuals": 5}
ATTENTION_KERNELS = set(KERNELS)
HOLDS = ()
HOLDS_NO = ()


def test_the_shared_cells_step_has_its_two_tiers_in_four_layers(compiled):
    """The four expert layers have their two tiers, forward and backward,
    with no array of all 163,840 routes' rows in a compact branch."""
    from torchmpi_tpu.parallel import ep

    cfg = compiled.cfg
    routes = cfg["sequence_length"] * cfg["num_experts_per_tok"]
    assert ep.compact_rows(routes, 8, 256) == 10240
    assert two_tiers(compiled.text, routes, (
        cfg["hidden_size"], cfg["moe_intermediate_size"])) == 2 * (
            cfg["num_hidden_layers"] - len(cfg["mlp_only_layers"]))


def test_the_shared_cell_reads_what_the_first_decoder_reads():
    """Full and window attention, the expert layer, the kernels, and nothing
    of the selecting one alone; but not ``moe_compact_share``, whose reader
    divides by every layer where this configuration's layer 0 has no
    experts."""
    spec = benchmark_spec()
    third = per_layer_of(spec, cell_of(CONFIG))
    assert per_layer_of(spec, cell_of("smallthinker-21b-a3b")) - third == {
        "moe_compact_share"}
    assert not third & {"attn_sparse_ms_per_step", "attn_index_ms_per_step",
                        "attn_select_ms_per_step"}
