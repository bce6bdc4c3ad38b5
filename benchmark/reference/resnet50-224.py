"""Plain float32 ResNet-50 v1.5 (He et al. 2015, arXiv:1512.03385):
forward, loss and gradient, with batch statistics as in training.

Follows the paper, with the departures the program's module makes, so that
the two compute the same function: SAME padding as XLA defines it (a
stride-2 3x3 on an even extent pads 0 before and 1 after, where the
paper's code pads 1 and 1), the stride on the 3x3 (v1.5), the variance as
E[x^2] - E[x]^2. Each residual block is recomputed in backward
(``jax.checkpoint``) so that a float32 batch of 256 fits the chip; that
changes no number.
"""

import jax
import jax.numpy as jnp

from benchmark import reference as ref

DN = ("NHWC", "HWIO", "NHWC")


def conv(x, w, stride, precision, padding="SAME"):
    return jax.lax.conv_general_dilated(
        ref.operand(x, precision), ref.operand(w, precision),
        (stride, stride), padding, dimension_numbers=DN,
    )


def batch_norm(x, p, eps):
    """Returns the normalised rows and the batch's statistics."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.maximum(
        jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean), 0.0)
    y = (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y, {"mean": mean, "var": var}


def bottleneck(x, p, stride, eps, precision):
    seen = {}
    y, seen["BatchNorm_0"] = batch_norm(
        conv(x, p["Conv_0"]["kernel"], 1, precision), p["BatchNorm_0"], eps)
    y, seen["BatchNorm_1"] = batch_norm(
        conv(jax.nn.relu(y), p["Conv_1"]["kernel"], stride, precision),
        p["BatchNorm_1"], eps)
    y, seen["BatchNorm_2"] = batch_norm(
        conv(jax.nn.relu(y), p["Conv_2"]["kernel"], 1, precision),
        p["BatchNorm_2"], eps)
    if "proj" in p:
        x, seen["proj_bn"] = batch_norm(
            conv(x, p["proj"]["kernel"], stride, precision),
            p["proj_bn"], eps)
    return jax.nn.relu(x + y), seen


def forward(params, x, m, precision):
    """The logits, and every norm's batch statistics under the names the
    program's module gives its running averages."""
    eps = m.get("batch_norm_epsilon", 1e-5)
    seen = {}
    x = conv(x, params["conv_init"]["kernel"], 2, precision,
             padding=[(3, 3), (3, 3)])
    x, seen["bn_init"] = batch_norm(x, params["bn_init"], eps)
    x = jax.lax.reduce_window(
        jax.nn.relu(x), -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        "SAME")
    n = 0
    for i, count in enumerate(m["stage_sizes"]):
        for j in range(count):
            stride = 2 if (i > 0 and j == 0) else 1
            block = jax.checkpoint(
                lambda x, p, s=stride: bottleneck(x, p, s, eps, precision))
            name = f"BottleneckBlock_{n}"
            x, seen[name] = block(x, params[name])
            n += 1
    x = jnp.mean(x, axis=(1, 2))
    head = params["Dense_0"]
    logits = ref.operand(x, precision) @ ref.operand(
        head["kernel"], precision) + head["bias"]
    return logits, seen


def loss_fn(params, x, y, m, precision, groups):
    """Mean cross-entropy, and the batch statistics. ``groups`` chips each
    normalise their own rows, as data-parallel replicas do, and average
    what they saw."""
    def one(xg, yg):
        logits, seen = forward(params, xg, m, precision)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(
            jnp.take_along_axis(logp, yg[:, None], axis=1)), seen

    xg = x.reshape((groups, -1) + x.shape[1:])
    yg = y.reshape((groups, -1))
    return jax.tree_util.tree_map(
        lambda a: jnp.mean(a, axis=0), jax.vmap(one)(xg, yg))


def running_at_seed(params, m):
    """The running averages as the module starts them: mean 0, variance
    1, shaped as ``forward`` reports the batch's."""
    x = jax.ShapeDtypeStruct(
        (2, m["image_size"], m["image_size"], 3), jnp.float32)
    shapes = jax.eval_shape(
        lambda p, x: forward(p, x, m, "float32")[1], params, x)
    return jax.tree_util.tree_map_with_path(
        lambda path, s: (jnp.ones if path[-1].key == "var" else jnp.zeros)(
            s.shape, jnp.float32),
        shapes)


def follow(cfg, params, batches, groups=1, precision="float32",
           moment_after=1):
    m = cfg["model"]
    with jax.default_matmul_precision("highest"):
        grad = jax.jit(jax.value_and_grad(
            lambda p, x, y: loss_fn(p, x, y, m, precision, groups),
            has_aux=True))
        feed = ((jnp.asarray(x, jnp.float32), jnp.asarray(y))
                for x, y in batches)
        return ref.follow(
            cfg, grad, params, feed, moment_after,
            running=(running_at_seed(params, m),
                     m.get("batch_norm_momentum", 0.9)))
