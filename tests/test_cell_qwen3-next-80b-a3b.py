"""``qwen3-next-80b-a3b.stream.x1`` at its rehearsal's sizes: the cases every
decoder configuration's cell has (``decoder_cases.py``), run here for this
one. It routes and, in one layer of four, attends: its traced rehearsal
reports the expert layer's counters, the attention kernels' share and the
chunks its delta rule runs over; it selects nothing and holds every head.
(More, of this configuration alone, is in
``tests/test_deltanet_decoder.py``.)"""

from decoder_cases import (  # noqa: F401 - collected here, for CONFIG
    test_a_step_that_changes_nothing_is_not_correct_in_the_cell,
    test_the_cells_rehearsal_is_correct,
    test_the_cells_traced_rehearsal_reports_the_routing_counters,
    test_the_fp8_control_is_not_correct_in_the_cell,
    test_zipf_token_ids_are_seeded_and_skewed,
)

CONFIG = "qwen3-next-80b-a3b"
MORE = {"moe_grouped_rows_per_step", "moe_max_over_mean_load",
        "moe_held_route_share", "moe_compact_share", "attn_kernel_share",
        "gdn_chunks_per_step", "conv_kernel_share"}
ABSENT = ("attn_selected_pair_share", "attn_heads_held_share", "ssm_",
          "retention_")
