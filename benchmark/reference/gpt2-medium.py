"""Plain float32 GPT-2 (Radford et al. 2019; sizes from
openai-community/gpt2-medium ``config.json``): pre-LN decoder blocks,
learned positions, tanh GELU (``gelu_new``), causal softmax attention,
next-token cross-entropy.

Departures, which are the program's module's: the output head is an untied
Dense with a bias, LayerNorm's epsilon is 1e-6, no dropout. The gradient
is accumulated over blocks of rows, and the layers run as one scan with each
recomputed in backward, so that float32 fits the chip and the program stays
small; rows do not interact, so that changes no number.
"""

import math

import jax
import jax.numpy as jnp

from benchmark import reference as ref

LN_EPS = 1e-6
ROWS = 2  # sequences per gradient block


def dense(x, p, precision):
    return ref.operand(x, precision) @ ref.operand(
        p["kernel"], precision) + p["bias"]


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True) - jnp.square(mean)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def block(x, p, heads, precision):
    b, t, d = x.shape
    qkv = dense(layer_norm(x, p["LayerNorm_0"]), p["Dense_0"], precision)
    q, k, v = (a.reshape(b, t, heads, -1) for a in jnp.split(qkv, 3, -1))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + dense(a.reshape(b, t, -1), p["Dense_1"], precision)
    h = gelu_new(dense(layer_norm(x, p["LayerNorm_1"]), p["Dense_2"],
                       precision))
    return x + dense(h, p["Dense_3"], precision)


def loss_fn(params, tokens, targets, m, precision):
    t = tokens.shape[1]
    x = params["Embed_0"]["embedding"][tokens] \
        + params["Embed_1"]["embedding"][jnp.arange(t)][None]
    # one scan over the layers' stacked parameters, each layer recomputed
    # in backward: the same arithmetic as a loop, a 24th of the program
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves),
        *[params[f"RingAttentionBlock_{i}"] for i in range(m["n_layer"])])
    layer = jax.checkpoint(
        lambda x, p: (block(x, p, m["n_head"], precision), None))
    x, _ = jax.lax.scan(layer, x, stacked)
    logits = dense(layer_norm(x, params["LayerNorm_0"]), params["Dense_0"],
                   precision)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def follow(cfg, params, batches, groups=1, precision="float32",
           moment_after=1):
    """``groups`` is not needed: no layer mixes rows, so the mean over the
    global batch is the same however the chips divide it."""
    m = cfg["model"]
    with jax.default_matmul_precision("highest"):
        grad_block = jax.jit(jax.value_and_grad(
            lambda p, x, y: loss_fn(p, x, y, m, precision)))
        add = jax.jit(
            lambda acc, g, l0, l1: (
                jax.tree_util.tree_map(jnp.add, acc, g), l0 + l1),
            donate_argnums=(0,))
        scale = jax.jit(
            lambda acc, n: jax.tree_util.tree_map(lambda a: a / n, acc),
            donate_argnums=(0,))

        def loss_and_grad(params, x, y):
            rows = min(ROWS, len(x))
            if len(x) % rows:
                raise ValueError(f"batch {len(x)} not a multiple of {rows}")
            acc, total = None, 0.0
            for i in range(0, len(x), rows):
                loss, g = grad_block(
                    params, jnp.asarray(x[i:i + rows]),
                    jnp.asarray(y[i:i + rows]))
                acc, total = (g, loss) if acc is None else add(
                    acc, g, total, loss)
            n = len(x) // rows
            return total / n, scale(acc, float(n))

        return ref.follow(cfg, loss_and_grad, params, batches,
                          moment_after)
