"""``lfm2-8b-a1b.stream.x1`` at its rehearsal's sizes: the cases every
decoder configuration's cell has (``decoder_cases.py``), run here for this
one. It routes under a bias that every step moves, convolves in four layers
of five and attends in one: its traced rehearsal reports the expert layer's
counters, the bias's two, the attention kernels' share and the
convolution's; it selects nothing and holds every head. (More, of this
configuration alone, is in ``tests/test_sconv_decoder.py``.)"""

from decoder_cases import (  # noqa: F401 - collected here, for CONFIG
    rehearsed_run,
    test_a_step_that_changes_nothing_is_not_correct_in_the_cell,
    test_the_cells_rehearsal_is_correct,
    test_the_cells_traced_rehearsal_reports_the_routing_counters,
    test_the_fp8_control_is_not_correct_in_the_cell,
    test_zipf_token_ids_are_seeded_and_skewed,
)

CONFIG = "lfm2-8b-a1b"
MORE = {"moe_grouped_rows_per_step", "moe_max_over_mean_load",
        "moe_held_route_share", "moe_compact_share", "attn_kernel_share",
        "conv_kernel_share", "moe_bias_max_abs", "moe_biased_route_share"}
ABSENT = ("attn_selected_pair_share", "attn_heads_held_share", "ssm_",
          "retention_", "gdn_")


def test_the_cells_state_is_compared_with_the_references(capsys):
    """The model state the forward pass reads is part of ``correct``: the
    run's verdict judges ``stat_norm_gap``, the worst of each router's bias,
    the routes it turned, the held experts' tokens and the rows, each
    against the reference's."""
    rc, line, out = rehearsed_run(
        capsys, CONFIG, "--seed", str(2**31 + 11), "--seconds", "1",
        "--trace", "0")
    assert rc == 0 and line["correct"] is True, out
    checked = [ln for ln in out.splitlines()
               if ln.startswith("# check stat_norm_gap ")]
    assert len(checked) == 1 and " ok " in checked[0], out
