"""Expert layer, what the router's bias turned (parallel/ep.py
``biased_sigmoid_route_weights``): of all the routes of the last step whose
loss the engine read (``tm_moe_routes_per_step``), the share whose expert is
not among the token's largest bare scores: routes the bias chose and the
scores alone would not have (gauge ``tm_moe_biased_routes_last_step``).
0 while the biases are 0; it grows as they spread. None where the program
has no such gauge (a router without a bias)."""

from benchmark import scopes


def read(run):
    turned = scopes.counter("tm_moe_biased_routes_last_step")
    routes = scopes.counter("tm_moe_routes_per_step")
    if turned is None or not routes:
        return None
    return 100.0 * turned / routes
