"""``falcon-h1-34b.stream.x1`` at its rehearsal's sizes: the cases every
decoder configuration's cell has (``decoder_cases.py``), run here for this
one. It routes nothing: its traced rehearsal reports the share of the mixer's
heads and the attention kernels' share, and nothing of an expert layer.
(More, of this configuration alone, is in ``tests/test_hybrid_decoder.py``.)"""

from decoder_cases import (  # noqa: F401 - collected here, for CONFIG
    test_a_step_that_changes_nothing_is_not_correct_in_the_cell,
    test_the_cells_rehearsal_is_correct,
    test_the_cells_traced_rehearsal_reports_the_routing_counters,
    test_the_fp8_control_is_not_correct_in_the_cell,
    test_zipf_token_ids_are_seeded_and_skewed,
)

CONFIG = "falcon-h1-34b"
MORE = {"ssm_heads_held_share", "attn_kernel_share", "conv_kernel_share"}
ABSENT = ("moe_", "attn_selected_pair_share", "attn_heads_held_share")
