"""The recomputed blocks' dense products (models/lm.py ``product``: the
query, key and value products, the residual after the mixer's output
product, the feed-forward's first products, as far as a model's file names
them), how far the step keeps their results for backward to read where it
would make the products again: of the bytes the named results would hold
over the layers of the step most recently traced (gauge
``tm_recompute_named_bytes_per_step``, set from static shapes while the step
is traced), the share whose kinds ``models.lm``'s rule kept, by the device's
memory, the parameters' bytes and what the step holds beside them (gauge
``tm_recompute_kept_bytes_per_step``). 100 % where everything named is
kept, 0 % where the rule declined all or the model's file names nothing
yet; None where the program has no such gauge (a model that recomputes no
block, or the parent of the PR that added the rule)."""

from benchmark import scopes


def read(run):
    named = scopes.counter("tm_recompute_named_bytes_per_step")
    kept = scopes.counter("tm_recompute_kept_bytes_per_step")
    if named is None or kept is None:
        return None
    return 100.0 * kept / named if named else 0.0
