"""``brumby-14b.stream.x1`` at its rehearsal's sizes: the cases every decoder
configuration's cell has (``decoder_cases.py``), run here for this one. It
neither routes nor attends: its traced rehearsal reports the share of its KV
heads and nothing of an expert layer or of attention. (More, of this
configuration alone, is in ``tests/test_retention_decoder.py``.)"""

from decoder_cases import (  # noqa: F401 - collected here, for CONFIG
    test_a_step_that_changes_nothing_is_not_correct_in_the_cell,
    test_the_cells_rehearsal_is_correct,
    test_the_cells_traced_rehearsal_reports_the_routing_counters,
    test_the_fp8_control_is_not_correct_in_the_cell,
    test_zipf_token_ids_are_seeded_and_skewed,
)

CONFIG = "brumby-14b"
MORE = {"retention_heads_held_share"}
ABSENT = ("moe_", "attn_")
