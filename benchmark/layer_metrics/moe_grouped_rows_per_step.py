"""Expert layer, its size (parallel/ep.py ``note_expert_layers``): the
program's gauge ``tm_moe_grouped_rows_per_step``, the rows each rank's
grouped products are sized for per step (every route, tokens x top_k x
layers: the worst case, nothing dropped), from static shapes when the
step is traced."""

from benchmark import scopes


def read(run):
    return scopes.counter("tm_moe_grouped_rows_per_step")
