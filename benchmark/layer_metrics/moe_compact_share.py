"""Expert layer, whether its compact tier of rows engaged (parallel/ep.py
``moe_local_experts``, ``note_expert_load``): of the configuration's expert
layers (``num_hidden_layers``: every layer of this decoder has one), the
share whose held routes fit the compact tier in the last step whose loss
the engine read (gauge ``tm_moe_compact_layers_last_step``), so that the
layer gathered, masked, multiplied and combined over that tier's rows and
not over all its routes. 100 % where routing is anywhere near its expected
share; a reading under it says a layer took the execution sized for every
route, and explains a slow run. 0 % where the layer has no compact tier
(half its experts held, or more). None where the program has no such gauge
(another model, or the parent of the PR that added the tier)."""

from benchmark import scopes


def read(run):
    compact = scopes.counter("tm_moe_compact_layers_last_step")
    layers = run["cfg"].get("num_hidden_layers")
    if compact is None or not layers:
        return None
    return 100.0 * compact / layers
