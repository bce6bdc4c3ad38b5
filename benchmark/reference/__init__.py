"""Plain references: ``<config>.py`` follows the first optimizer steps of
its configuration in straightforward float32 ``jax.numpy``. They import
nothing of the program and are found by the configuration's name."""

from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent

# The control's formats: e4m3 for what goes forward, e5m2 for the gradient
# that comes back, each tensor scaled to its format's range, as fp8
# training recipes do (Micikevicius et al. 2022, arXiv:2209.05433).
FORWARD = (jnp.float8_e4m3fn, 448.0)
BACKWARD = (jnp.float8_e5m2, 57344.0)


def _round_to(x, fmt):
    dtype, top = fmt
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _fp8(x):
    return _round_to(x, FORWARD)


_fp8.defvjp(lambda x: (_round_to(x, FORWARD), None),
            lambda _, g: (_round_to(g, BACKWARD),))


def operand(x, precision: str):
    """An operand of a matrix product or convolution as the stated
    precision sees it. ``float32`` leaves it alone. ``fp8`` is the control,
    the nearest precision under the configurations' bfloat16: the operand
    is rounded to e4m3 on the way forward and its gradient to e5m2 on the
    way back."""
    if precision == "float32":
        return x
    if precision == "fp8":
        return _fp8(x)
    raise ValueError(f"unknown precision {precision!r}")


def leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree
    )


def to_host(tree):
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), tree)


def sgd_momentum(lr, momentum):
    """optax.sgd(lr, momentum): trace = g + momentum * trace; p -= lr * trace."""

    def init(params):
        return jax.tree_util.tree_map(jnp.zeros_like, params)

    def update(params, trace, grads, count):
        trace = jax.tree_util.tree_map(
            lambda t, g: g + momentum * t, trace, grads
        )
        params = jax.tree_util.tree_map(
            lambda p, t: p - lr * t, params, trace
        )
        return params, trace

    return init, update, lambda trace: trace


def adamw(lr, b1, b2, eps, weight_decay):
    """optax.adamw: bias-corrected moments, eps outside the root, decoupled
    weight decay on every leaf, all scaled by -lr."""

    def init(params):
        return (jax.tree_util.tree_map(jnp.zeros_like, params),
                jax.tree_util.tree_map(jnp.zeros_like, params))

    def update(params, state, grads, count):
        mu, nu = state
        mu = jax.tree_util.tree_map(
            lambda m, g: b1 * m + (1 - b1) * g, mu, grads
        )
        nu = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads
        )
        c1, c2 = 1 - b1**count, 1 - b2**count
        params = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * (
                (m / c1) / (jnp.sqrt(v / c2) + eps) + weight_decay * p
            ),
            params, mu, nu,
        )
        return params, (mu, nu)

    return init, update, lambda state: state[0]


def make_optimizer(spec):
    if spec["name"] == "sgd":
        return sgd_momentum(spec["learning_rate"], spec["momentum"])
    if spec["name"] == "adamw":
        return adamw(spec["learning_rate"], spec["b1"], spec["b2"],
                     spec["eps"], spec["weight_decay"])
    raise ValueError(f"no plain optimizer for {spec['name']!r}")


def follow(cfg, loss_and_grad, params, batches, moment_after=1,
           running=None):
    """Drive ``loss_and_grad(params, x, y) -> (loss, grads)`` and the plain
    optimizer over ``batches`` from ``params``. Returns what the harness
    compares: each step's loss, the norm leaf by leaf of the optimizer's
    first-moment buffer after ``moment_after`` steps (after one, it is the
    first gradient as the optimizer got it), and the norm of each leaf's
    change over all the steps.

    A model that keeps running statistics gives ``running``, (their tree
    at the seed, the momentum of the average), and a ``loss_and_grad`` that
    returns ``((loss, batch statistics), grads)``; the norm of each
    statistic's change over all the steps is then returned too."""
    init, update, first_moment = make_optimizer(cfg["optimizer"])
    update = jax.jit(update, donate_argnums=(0, 1), static_argnums=3)
    start = params  # the caller's stays; the copy is updated in place
    params = jax.tree_util.tree_map(jnp.copy, params)
    state = init(params)
    losses, moment_norms = [], None
    stats_start, momentum = running or (None, None)
    stats = stats_start
    for i, (x, y) in enumerate(batches):
        loss, grads = loss_and_grad(params, x, y)
        if running is not None:
            loss, seen = loss
            stats = jax.tree_util.tree_map(
                lambda r, s: momentum * r + (1 - momentum) * s, stats, seen)
        losses.append(float(loss))
        params, state = update(params, state, grads, i + 1)
        del grads
        if i + 1 == moment_after:
            moment_norms = to_host(jax.jit(leaf_norms)(first_moment(state)))
    change = jax.jit(
        lambda a, b: leaf_norms(jax.tree_util.tree_map(jnp.subtract, a, b))
    )
    return {
        "losses": losses,
        "moment_norms": moment_norms,
        "update_norms": to_host(change(params, start)),
        "stat_norms": None if running is None
        else to_host(change(stats, stats_start)),
    }
