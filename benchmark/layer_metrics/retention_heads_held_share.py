"""Power retention, the share of the KV heads held here: the KV heads the
program built its layers with, summed over the layers of the step most
recently traced (gauge ``tm_retention_kv_heads_held_per_step``,
parallel/retention.py ``note_retention_step``, set by models/retentive.py
``RetentionDecoder.__call__``), over the KV heads of the same layers whole,
which the configuration's file gives (``published.num_key_value_heads`` a
layer). 12.5 % for 1 of 8 KV heads in each of 4 layers. None where the
program has no such gauge (a model with no retention, or the parent of the
PR that added the gauge) or the file no such number."""

from benchmark import scopes


def read(run):
    held = scopes.counter("tm_retention_kv_heads_held_per_step")
    whole = run["cfg"].get("published", {}).get("num_key_value_heads")
    if held is None or not isinstance(whole, int):
        return None
    return 100.0 * held / (whole * run["cfg"]["num_hidden_layers"])
