"""Attention, the share of the heads held here: the query heads the program
built its layers with, summed over the layers of the step most recently
traced (gauge ``tm_attn_query_heads_held_per_step``, models/decoder.py
``MoEDecoder.__call__``), over the heads of the same layers whole, which the
configuration's file gives (``published.num_attention_heads_per_layer``, a
pattern repeated over the depth). 12.5 % for one KV head of 8 with its
group (6 + 9 + 9 + 9 + 6 = 39 of 312). None where the program has no such
gauge or the file no such pattern (a model that holds every head, or the
parent of the PR that added the gauge)."""

from benchmark import scopes


def read(run):
    held = scopes.counter("tm_attn_query_heads_held_per_step")
    pattern = run["cfg"].get("published", {}).get(
        "num_attention_heads_per_layer")
    if held is None or not isinstance(pattern, list):
        return None
    layers = run["cfg"]["num_hidden_layers"]
    whole = sum(pattern[i % len(pattern)] for i in range(layers))
    return 100.0 * held / whole
