"""State-space mixer, the share of the heads held here: the mixer heads the
program built its layers with, summed over the layers of the step most
recently traced (gauge ``tm_ssm_heads_held_per_step``, parallel/ssm.py
``note_ssm_step``, set by models/hybrid.py ``HybridDecoder.__call__``), over
the heads of the same layers whole, which the configuration's file gives
(``published.mamba_n_heads`` a layer). 12.5 % for 4 of 32 heads in each of 4
layers. None where the program has no such gauge or the file no such
number (a model with no mixer, or the parent of the PR that added the
gauge)."""

from benchmark import scopes


def read(run):
    held = scopes.counter("tm_ssm_heads_held_per_step")
    whole = run["cfg"].get("published", {}).get("mamba_n_heads")
    if held is None or not isinstance(whole, int):
        return None
    return 100.0 * held / (whole * run["cfg"]["num_hidden_layers"])
