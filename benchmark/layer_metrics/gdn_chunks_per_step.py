"""The gated delta rule, the chunks a step's recurrences run over: the
layers that have the rule x the sequences x the chunks a sequence (gauge
``tm_gdn_chunks_per_step``, parallel/deltanet.py ``note_gdn_step``, set from
static shapes while the step is traced by models/deltanet.py
``GatedDeltaDecoder.__call__``). 768 for 3 layers of 1 sequence of 16,384
positions by chunks of 64; it moves when the chunk does. None where the
program has no such gauge (a model with no such layer, or the parent of the
PR that added the gauge)."""

from benchmark import scopes


def read(run):
    return scopes.counter("tm_gdn_chunks_per_step")
