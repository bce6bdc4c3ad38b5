"""``keye-vl-2-30b-a3b.stream.x1``'s training step at its real size for the
described chip: the cases every decoder configuration's step has
(``decoder_cases.py``), run here for this one on one lowering and one
compilation (the longest of the suite: this file is its own for that), then
what only a selecting step can hold or leave out."""

import re
from collections import Counter

from decoder_cases import (  # noqa: F401 - collected here, for CONFIG
    benchmark_spec,
    cell_of,
    compiled,
    lowered,
    one_chip,
    outside_fusions,
    per_layer_of,
    test_the_cells_step_fits_the_chip,
    test_the_cells_step_keeps_the_products_the_rule_counted,
    test_the_cells_step_lowers_for_the_chip_to_the_text_it_had,
    test_the_configuration_is_a_cell_of_the_benchmark,
)

CONFIG = "keye-vl-2-30b-a3b"
# as the parent of PR 38 lowered it, and as PR 40 left it: its layers all
# select, their kernels kept their results under the policy already, and the
# one helper that now spells every model's recomputation
# (``models.lm.recomputed``) gives it the text it had; PR 42's rule of the
# token lookup (``embedding.takes_sorted_sum``) keeps jax's transpose at this
# table's width, and the step was the parent's text letter for letter; since
# PR 43 the head and its loss are one function with a derivative rule of its
# own (``models/lm_head.py``), a loop over blocks of 8,192 rows where the
# float32 logits of every row stood (1,248,452 47bf5842f8ac3442 before)
# since PR 47 a block's backward reads the router's logits, the one kind
# ``models.lm``'s rule keeps here (1,250,741 fe66531536376d56 before)
PIN = (1250467, "75a91119112db72c")
OWN = ["attn_index_kernel_ms_per_step", "attn_index_loss",
       "attn_index_ms_per_step", "attn_select_ms_per_step",
       "attn_selected_pair_share", "attn_sparse_kernel_roofline",
       "attn_sparse_ms_per_step"]
PARAMETERS = (465e6, 466e6)  # 4 layers of 96.9 M + 77.8 M of vocabulary
# 12 B a parameter of state (5.20 GiB) and 7.13 GiB of temporaries measured
# here, 12.33 GiB: 3.4 GiB inside the chip's 15.75. Of the temporaries
# 2.12 GiB are the four layers' kept panels of index scores (5.01 GiB
# without them, with a second run of their kernel)
FITS_IN = 12.6 * 2**30
# the temporaries of the step with no product kept, 11.65 GiB with the
# arguments (``scripts/recompute_probe.py keye-vl-2-30b-a3b --keep none
# --compile``); the rule's estimate of that step is 13.79 GiB, so of the
# four kinds the file names it keeps the router's logits alone (32 MiB:
# the indexer's projections, 0.31 GiB, would pass 14.0 by the estimate)
NOTHING_KEPT = 6_927_643_648
PRODUCTS = (115, 119)  # the router's product once a layer
# every piece of the selected attention is a kernel of the repo's own (none
# of jax's splash kernels is left); the index scores, the selection and both
# forward kernels run once a step (the layer's recomputation keeps what they
# made, the sixteen panels of float32 scores among it): 4 layers of 4 panels
# of 4,096 queries
KERNELS = dict.fromkeys((
    "tm_attn_index_scores", "tm_attn_select_kth", "tm_attn_sparse_fwd",
    "tm_attn_sparse_mean_probabilities", "tm_attn_sparse_bwd",
    "tm_attn_index_grad_queries", "tm_attn_index_grad_keys"), 4 * 4)
# the forward and the backward attention kernel, no other
ATTENTION_KERNELS = {"tm_attn_sparse_fwd", "tm_attn_sparse_bwd"}
HOLDS = ("ragged-dot",)
HOLDS_NO = (r"(?:s8|u8)\[4096,\d+\]",)  # no int8 mask of a panel anywhere


def test_the_selecting_cells_selection_is_nowhere_an_array(compiled):
    """The kernels make the selection in VMEM from a tile of scores: no
    int32 table or boolean mask of a panel's ``[4096, keys]`` is an
    instruction's result outside a fusion (inside one, a comparison of the
    scores is counted where it is made); float32 panels are left: the
    scores, and in backward the indexer's gradient of them."""
    from torchmpi_tpu.parallel import selected_attention as sa

    cfg = compiled.cfg
    panel = sa._panel_of(cfg["sequence_length"])
    assert (panel, cfg["sequence_length"] // panel) == (4096, 4)
    assert set(KERNELS.values()) == {cfg["num_hidden_layers"] * 4}
    assert all(k.startswith(sa.SPARSE_KERNEL_EVENTS) == (
        "index" not in k and "select" not in k) for k in KERNELS)
    whole = re.compile(r"= \(?(s32|pred|f32)\[%d,\d{4,}\]" % panel)
    held = Counter(m.group(1) for m in map(whole.search, outside_fusions(
        compiled.text)) if m)
    assert set(held) == {"f32"}, held


def test_the_two_routed_decoders_share_the_expert_layers_metrics():
    """... and the kernels', not the scopes of each other's attention."""
    spec = benchmark_spec()
    shared = per_layer_of(spec, cell_of(CONFIG)) & per_layer_of(
        spec, cell_of("smallthinker-21b-a3b"))
    assert {"moe_route_ms_per_step", "moe_compact_share",
            "attn_kernel_share", "attn_kernel_ms_per_step"} <= shared
    assert not shared & {"attn_full_ms_per_step", "attn_window_ms_per_step",
                         "attn_sparse_ms_per_step"}
