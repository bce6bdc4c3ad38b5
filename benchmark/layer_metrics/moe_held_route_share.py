"""Expert layer, the work that arrived (parallel/ep.py
``note_expert_load`` over ``note_expert_layers``): the routes that fell on
experts held here in the last step whose loss the engine read
(``tm_moe_held_routes_last_step``) as a share of all the step's routes
(``tm_moe_routes_per_step``). The nominal share is held / experts, 12.5 %
in the benchmark's cell; the grouped products' operations follow the real
one, so a run's rate does too."""

from benchmark import scopes


def read(run):
    held = scopes.counter("tm_moe_held_routes_last_step")
    routes = scopes.counter("tm_moe_routes_per_step")
    if held is None or not routes:
        return None
    return 100.0 * held / routes
