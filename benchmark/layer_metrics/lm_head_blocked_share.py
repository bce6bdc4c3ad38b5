"""Vocabulary head, the share of the step's token rows whose loss came from
the head's own derivative rule (models/lm_head.py ``head_loss``: the
product with the vocabulary matrix, the float32 log-softmax, the pick of
the target and the three gradients a block of rows at a time, forward; no
``[rows, V]`` logits or gradient as whole arrays): the rows of the step
most recently traced that went through the rule (gauge
``tm_lm_head_blocked_rows_per_step``, set from static shapes while the
step is traced) over the step's tokens a chip, ``per_chip_batch x
sequence_length``. 100 % where the model's loss is the head's own; None
where the program has no such gauge (a model with no vocabulary head, or
the parent of the PR that added the rule)."""

from benchmark import scopes


def read(run):
    rows = scopes.counter("tm_lm_head_blocked_rows_per_step")
    cfg = run["cfg"]
    tokens = cfg.get("per_chip_batch", 0) * cfg.get("sequence_length", 0)
    if rows is None or not tokens:
        return None
    return 100.0 * rows / tokens
