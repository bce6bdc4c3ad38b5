"""Plain float32 Laguna-S-2.1, one chip's share (sizes from
poolside/Laguna-S-2.1 ``config.json``; the cut and every reading the config
leaves open are in ``configs/laguna-s-2-1.json``).

One layer, input ``h`` ``[t, d]``, this chip holding one KV head with its
group of query heads (6 in a full-attention layer, 9 in a sliding one),
1,536 columns of layer 0's feed-forward and 8 of the 256 experts: ``a =
RMSNorm(h)``; ``q = a W_q``, ``k = a W_k``, ``v = a W_v`` over the held heads,
no biases. A sliding layer rotates the whole 128 of each head (theta 1e4,
the head's halves against each other) and sees key ``j`` from query ``i``
iff ``0 <= i - j < 512``; a full layer rotates the first 64 (their halves
against each other; the other 64 pass) by YaRN's frequencies, cos and sin
times the attention factor, and sees ``j <= i``. Head ``n``'s output ``o_n =
softmax(q_n . k / sqrt(128)) v`` is multiplied by its gate ``sigmoid(a
W_g)_n``, one number a token and head; ``h' = h + concat(o) W_o``. ``m =
RMSNorm(h')``. Layer 0: ``out = h' + (silu(m D_g) * (m D_u)) D_d`` over the
held columns. The others: ``s = sigmoid(m W_r)`` over all 256; the token's
experts are its 10 largest ``s``, ``w_e = 2.5 s_e / sum_chosen s``; ``out =
h' + (silu(m S_g) * (m S_u)) S_d + sum over the chosen experts HELD HERE of
w_e (silu(m W_g^e) * (m W_u^e)) W_d^e``. What the heads, columns and experts
held elsewhere would add is left out. Then RMSNorm, the untied head over
the vocabulary's slice, mean next-token cross-entropy. No auxiliary loss.

YaRN over ``D`` rotated dimensions: ``f_i = theta^(-2i / D)``; ``low =
floor(c(beta_fast))``, ``high = ceil(c(beta_slow))`` with ``c(n) = D ln(P /
(2 pi n)) / (2 ln theta)`` and ``P`` the original positions, both kept in
``[0, D - 1]``; ``r_i = clip((i - low) / (high - low), 0, 1)``; the
frequency is ``r_i f_i / factor + (1 - r_i) f_i``.

Nothing of the program is imported. Attention runs a block of queries at a
time against all the keys under a mask; the experts are a loop over those
held, each over every token, under a mask: no sort, no grouped product. The
gradient is accumulated a sequence at a time and each layer is recomputed
in backward, so that float32 fits the chip; rows do not interact, so that
changes no number.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as ref

QUERY_BLOCK = 512


def matmul(x, w, precision):
    return ref.operand(x, precision) @ ref.operand(w, precision)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def frequencies(rope, head_dim):
    """(the rotated width, its ``width / 2`` frequencies) of one of the
    config's ``rope_parameters``."""
    width = int(head_dim * rope["partial_rotary_factor"])
    theta = float(rope["rope_theta"])
    i = np.arange(width // 2, dtype=np.float64)
    freq = theta ** (-2.0 * i / width)
    if rope["rope_type"] == "default":
        return width, freq.astype(np.float32)
    positions = rope["original_max_position_embeddings"]

    def turns_at(n):
        return width * math.log(positions / (2 * math.pi * n)) / (
            2 * math.log(theta))

    low = min(max(math.floor(turns_at(rope["beta_fast"])), 0), width - 1)
    high = min(max(math.ceil(turns_at(rope["beta_slow"])), 0), width - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return width, (
        ramp * freq / rope["factor"] + (1.0 - ramp) * freq
    ).astype(np.float32)


def rotary(x, rope):
    """x: [t, heads, d]; positions 0 .. t - 1."""
    width, inv = frequencies(rope, x.shape[-1])
    factor = rope.get("attention_factor", 1.0)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos = factor * jnp.cos(angle)[:, None, :]
    sin = factor * jnp.sin(angle)[:, None, :]
    half = width // 2
    x1, x2, rest = x[..., :half], x[..., half:width], x[..., width:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


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


def gated(m, p, name, precision):
    """``(silu(m W_gate) * (m W_up)) W_down`` of the dense matrices
    ``name``."""
    hidden = jax.nn.silu(matmul(m, p[name + "_gate"]["kernel"], precision)) \
        * matmul(m, p[name + "_up"]["kernel"], precision)
    return matmul(hidden, p[name + "_down"]["kernel"], precision)


def experts(m, r, p, cfg, precision):
    """The held routed experts' part of the layer's result for every
    token, from the router's logits ``r`` over all the experts."""
    score = jax.nn.sigmoid(r)
    top, chosen = jax.lax.top_k(score, cfg["num_experts_per_tok"])
    weight = cfg["moe_routed_scaling_factor"] * top / jnp.sum(
        top, axis=-1, keepdims=True)
    y = jnp.zeros_like(m)
    for i, e in enumerate(cfg["model"]["experts_held"]):
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        hidden = jax.nn.silu(matmul(m, p["experts_gate"][i], precision)) \
            * matmul(m, p["experts_up"][i], precision)
        y = y + w_e[:, None] * matmul(hidden, p["experts_down"][i], precision)
    return y


def attention_part(h, p, cfg, kind, precision):
    """What the held heads add to the residual stream: ``concat(g_n o_n)
    W_o`` over them."""
    t, kv = h.shape[0], cfg["num_key_value_heads"]
    a = rms_norm(h, p["norm_attn"]["scale"], cfg["rms_norm_eps"])
    q = matmul(a, p["q"]["kernel"], precision)
    heads = q.shape[-1] // cfg["head_dim"]
    q = q.reshape(t, heads, -1)
    k = matmul(a, p["k"]["kernel"], precision).reshape(t, kv, -1)
    v = matmul(a, p["v"]["kernel"], precision).reshape(t, kv, -1)
    rope = cfg["rope_parameters"][kind]
    q, k = rotary(q, rope), rotary(k, rope)
    o = attention(
        q.reshape(t, kv, heads // kv, -1), k, v,
        cfg["sliding_window"] if kind == "sliding_attention" else None)
    gate = jax.nn.sigmoid(matmul(a, p["head_gate"]["kernel"], precision))
    o = o.reshape(t, heads, -1) * gate[:, :, None]
    return matmul(o.reshape(t, -1), p["o"]["kernel"], precision)


def feed_forward_part(h, p, cfg, sparse, precision):
    """What this chip's feed-forward adds: the held columns of a dense
    layer, or the shared expert whole and the held routed experts."""
    m = rms_norm(h, p["norm_moe"]["scale"], cfg["rms_norm_eps"])
    if not sparse:
        return gated(m, p, "mlp", precision)
    r = m @ p["router"]["kernel"]  # float32, whatever the control rounds
    return gated(m, p, "shared", precision) + experts(m, r, p, cfg, precision)


def layer(h, p, cfg, kind, sparse, precision):
    h = h + attention_part(h, p, cfg, kind, precision)
    return h + feed_forward_part(h, p, cfg, sparse, precision)


def loss_fn(params, tokens, targets, cfg, precision):
    """One sequence: tokens and targets are [t]."""
    h = params["embed"]["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        h = jax.checkpoint(
            lambda h, p, kind=cfg["layer_types"][i],
            sparse=cfg["mlp_layer_types"][i] == "sparse":
            layer(h, p, cfg, kind, sparse, precision)
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
