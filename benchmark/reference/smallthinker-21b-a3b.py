"""Plain float32 SmallThinker-21BA3B-Instruct, one chip's share (sizes from
PowerInfer/SmallThinker-21BA3B-Instruct ``config.json``; the cut and every
reading the config leaves open are in ``configs/smallthinker-21b-a3b.json``).

One layer, input ``h`` ``[t, d]``: the router reads ``h`` as it arrives,
``r = h W_r``; ``a = RMSNorm(h)``; queries over 28 heads, keys and values
over 4, query head ``n`` reading KV head ``n // 7``; a layer with
``rope_layout`` 1 rotates queries and keys (theta 1.5e6, the head's halves
against each other) and sees key ``j`` from query ``i`` iff ``0 <= i - j <
4096``; a layer with 0 has no position at all and sees ``j <= i``; ``h' = h
+ Attn W_o``; ``m = RMSNorm(h')``; the token's experts are its 6 largest
``r``, weighted by the softmax over those 6; ``out = h' + sum over the
chosen experts HELD HERE of w_e (relu(m W_g^e) * (m W_u^e)) W_d^e``. What the
experts held elsewhere would add is left out. Then RMSNorm, the untied head
over the vocabulary's slice, mean next-token cross-entropy. No auxiliary
loss.

Nothing of the program is imported. Attention runs a block of queries at a
time against all the keys under a mask (28 x 8192 x 8192 float32 scores
would be 7.5 GB a sequence); the experts are a loop over those held, each
over every token, under a mask: no sort, no grouped product. The gradient
is accumulated a sequence at a time and each layer is recomputed in
backward, so that float32 fits the chip; rows do not interact, so that
changes no number.
"""

import math

import jax
import jax.numpy as jnp

from benchmark import reference as ref

QUERY_BLOCK = 512


def matmul(x, w, precision):
    return ref.operand(x, precision) @ ref.operand(w, precision)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """x: [t, heads, d]; positions 0 .. t - 1."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window):
    """q: [t, kv_heads, group, d]; k, v: [t, kv_heads, d]. A block of
    queries at a time, each recomputed in backward."""
    t = q.shape[0]
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    key_pos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(args):
        qs, start = args
        pos = start + jnp.arange(qb)[:, None]
        seen = key_pos <= pos
        if window is not None:
            seen = seen & (pos - key_pos < window)
        s = jnp.einsum("qhgd,khd->hgqk", qs, k) / math.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    out = jax.lax.map(
        block, (q.reshape((t // qb, qb) + q.shape[1:]),
                jnp.arange(0, t, qb)))
    return out.reshape(q.shape)


def experts(m, r, p, cfg, precision):
    """The held experts' part of the layer's result for every token."""
    top, chosen = jax.lax.top_k(r, cfg["moe_num_active_primary_experts"])
    weight = jax.nn.softmax(top, axis=-1)
    y = jnp.zeros_like(m)
    for i, e in enumerate(cfg["model"]["experts_held"]):
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        hidden = jax.nn.relu(matmul(m, p["experts_gate"][i], precision)) \
            * matmul(m, p["experts_up"][i], precision)
        y = y + w_e[:, None] * matmul(hidden, p["experts_down"][i], precision)
    return y


def layer(h, p, cfg, windowed, rotated, precision):
    t = h.shape[0]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = cfg["rms_norm_eps"]
    r = h @ p["router"]["kernel"]  # float32, whatever the control rounds
    a = rms_norm(h, p["norm_attn"]["scale"], eps)
    q = matmul(a, p["q"]["kernel"], precision).reshape(t, heads, -1)
    k = matmul(a, p["k"]["kernel"], precision).reshape(t, kv, -1)
    v = matmul(a, p["v"]["kernel"], precision).reshape(t, kv, -1)
    if rotated:
        q, k = rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"])
    o = attention(
        q.reshape(t, kv, heads // kv, -1), k, v,
        cfg["sliding_window_size"] if windowed else None)
    h = h + matmul(o.reshape(t, -1), p["o"]["kernel"], precision)
    m = rms_norm(h, p["norm_moe"]["scale"], eps)
    return h + experts(m, r, p, cfg, precision)


def loss_fn(params, tokens, targets, cfg, precision):
    """One sequence: tokens and targets are [t]."""
    h = params["embed"]["embedding"][tokens]
    rope, window = cfg["rope_layout"], cfg["sliding_window_layout"]
    for i in range(cfg["num_hidden_layers"]):
        h = jax.checkpoint(
            lambda h, p, w=window[i % len(window)], r=rope[i % len(rope)]:
            layer(h, p, cfg, w, r, precision)
        )(h, params[f"MoEDecoderBlock_{i}"])
    logits = matmul(
        rms_norm(h, params["norm"]["scale"], cfg["rms_norm_eps"]),
        params["head"]["kernel"], precision)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def follow(cfg, params, batches, groups=1, precision="float32",
           moment_after=1):
    """``groups`` is not needed: no layer mixes sequences, so the mean over
    the global batch is the same however the chips divide it."""
    with jax.default_matmul_precision("highest"):
        grad_row = jax.jit(jax.value_and_grad(
            lambda p, x, y: loss_fn(p, x, y, cfg, precision)))
        add = jax.jit(
            lambda acc, g, l0, l1: (
                jax.tree_util.tree_map(jnp.add, acc, g), l0 + l1),
            donate_argnums=(0,))
        scale = jax.jit(
            lambda acc, n: jax.tree_util.tree_map(lambda a: a / n, acc),
            donate_argnums=(0,))

        def loss_and_grad(params, x, y):
            acc, total = None, 0.0
            for i in range(len(x)):
                loss, g = grad_row(
                    params, jnp.asarray(x[i]), jnp.asarray(y[i]))
                acc, total = (g, loss) if acc is None else add(
                    acc, g, total, loss)
            return total / len(x), scale(acc, float(len(x)))

        return ref.follow(cfg, loss_and_grad, params, batches,
                          moment_after)
