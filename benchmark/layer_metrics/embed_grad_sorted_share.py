"""Embedding, the share of the step's token rows whose gradient is summed
by sorted ids before it touches the table (models/embedding.py
``TokenEmbed``: the rows put in their ids' order, the runs of equal ids
summed by blocks on the MXU, each distinct id's sum brought to the table by
one gather; no scatter-add of rows): the rows of the step most recently
traced that took that backward (gauge ``tm_embed_grad_sorted_rows_per_step``,
set from static shapes while the step is traced) over the step's tokens a
chip, ``per_chip_batch x sequence_length``. 100 % where the lookup's own
derivative rule is in the step; 0 % where the rows' width keeps jax's
transpose of the gather, XLA's scatter-add, because it was measured the
faster there (``embedding.takes_sorted_sum``: bfloat16 rows of 1,024,
2,048, 3,072 or 4,096). None where the program has no such gauge (a model
with no token lookup, or the parent of the PR that added the rule)."""

from benchmark import scopes


def read(run):
    rows = scopes.counter("tm_embed_grad_sorted_rows_per_step")
    cfg = run["cfg"]
    tokens = cfg.get("per_chip_batch", 0) * cfg.get("sequence_length", 0)
    if rows is None or not tokens:
        return None
    return 100.0 * rows / tokens
