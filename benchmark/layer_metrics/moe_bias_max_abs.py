"""Expert layer, the router's bias (parallel/ep.py
``biased_sigmoid_route_weights``, moved once a step by models/decoder.py
``make_moe_lm_loss_fn``): the largest magnitude among the biases of every
expert layer's router, over ALL its experts, as the last step whose loss the
engine read left them (gauge ``tm_moe_bias_max_abs``). Each step moves a
bias by the update rate (0.001) up or down, so after ``n`` steps it is at
most ``n`` rates: an expert that stays over or under the mean load all
along. 0 would say the rule never ran. None where the program has no such
gauge (a router without a bias)."""

from benchmark import scopes


def read(run):
    return scopes.counter("tm_moe_bias_max_abs")
