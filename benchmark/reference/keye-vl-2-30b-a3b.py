"""Plain float32 Keye-VL-2.0-30B-A3B language model, one chip's share (sizes
from Kwai-Keye/Keye-VL-2.0-30B-A3B ``config.json``; the cut and every
reading the config leaves open are in ``configs/keye-vl-2-30b-a3b.json``).

One layer, input ``x`` ``[t, d]``: ``a = RMSNorm(x)``; queries over 32 heads,
keys and values over 4 (query head ``n`` reads KV head ``n // 8``), each
query and key head through an RMSNorm of its own, then rotary position
(theta 1e7, the head's halves against each other). The indexer reads
``stop_gradient(a)``: ``qI = a W_qI`` (16 heads of 64), ``kI = LayerNorm(a
W_kI)`` (one head), ``w = a W_w``, ``qI`` and ``kI`` rotated over their 64;
``I[i, j] = (sum_n w[i, n] relu(qI[i, n] . kI[j])) * 64^-1/2 * 16^-1/2`` for
``j <= i``. Query ``i`` sees ``S_i``, the ``min(i + 1, topk)`` keys ``j <= i``
with the largest ``I[i, j]`` (ties: the lower ``j``); ``x' = x + Attn_S W_o``;
``m = RMSNorm(x')``; ``r = m W_r``; the token's experts are its 8 largest
``r``, weighted by the softmax over those 8; ``out = x' + sum over the chosen
experts HELD HERE of w_e (silu(m W_g^e) * (m W_u^e)) W_d^e``. Then RMSNorm,
the untied head over the vocabulary's slice.

Loss: ``L_lm + sum over layers of L_I``; ``L_lm`` the mean next-token
cross-entropy, ``L_I = mean_i KL(p_i || softmax_{j in S_i} I[i, j])`` with
``p_i`` the layer's attention probabilities summed over the heads over their
number, AS A CONSTANT. The two ``stop_gradient``s (the indexer's input, the
probabilities) are where the equations put them: ``L_lm`` gives the indexer
no gradient, ``L_I`` gives nothing else any.

Nothing of the program is imported. A block of queries at a time against
all the keys: its index scores, ``lax.top_k`` for the threshold, a mask, a
softmax; the experts are a loop over those held, under a mask. The gradient
is accumulated a sequence at a time and each layer is recomputed in
backward. The indexer and the router stay float32 whatever the control
rounds: a control that selected other keys would be another model.
"""

import math

import jax
import jax.numpy as jnp

from benchmark import reference as ref

QUERY_BLOCK = 256


def matmul(x, w, precision):
    return ref.operand(x, precision) @ ref.operand(w, precision)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def rotary(x, theta):
    """x: [t, heads, d]; positions 0 .. t - 1."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def selection(scores, pos, topk):
    """The mask of ``S_i`` for a block of queries: ``scores`` ``[qb, t]``
    with ``-inf`` at ``j > i``, ``pos`` ``[qb, 1]`` the queries' positions."""
    k = min(topk, scores.shape[-1])
    want = jnp.minimum(pos + 1, k)
    largest = jax.lax.top_k(scores, k)[0]
    thr = jnp.take_along_axis(largest, want - 1, axis=-1)
    over, tie = scores > thr, scores == thr
    room = want - jnp.sum(over, axis=-1, keepdims=True)
    return over | (tie & (jnp.cumsum(tie, axis=-1) <= room))


def attention(q, k, v, qi, ki, wi, topk):
    """q: [t, kv_heads, group, d]; k, v: [t, kv_heads, d]; the indexer's qi
    [t, hI, dI], ki [t, dI], wi [t, hI]. Returns (output, L_I). A block of
    queries at a time, each recomputed in backward."""
    t = q.shape[0]
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    key_pos = jnp.arange(t)[None, :]
    scale = qi.shape[-1] ** -0.5 * qi.shape[1] ** -0.5

    @jax.checkpoint
    def block(args):
        qs, qis, wis, start = args
        pos = start + jnp.arange(qb)[:, None]
        index = jnp.sum(
            jax.nn.relu(jnp.einsum("qnd,kd->qnk", qis, ki))
            * wis[:, :, None], axis=1) * scale
        index = jnp.where(key_pos <= pos, index, -jnp.inf)
        seen = selection(jax.lax.stop_gradient(index), pos, topk)
        s = jnp.einsum("qhgd,khd->hgqk", qs, k) / math.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out = jnp.einsum("hgqk,khd->qhgd", p, v)
        target = jax.lax.stop_gradient(jnp.mean(p, axis=(0, 1)))
        log_r = jax.nn.log_softmax(
            jnp.where(seen, index, -jnp.inf), axis=-1)
        kl = jnp.where(
            seen, jax.scipy.special.xlogy(target, target)
            - target * jnp.where(seen, log_r, 0.0), 0.0)
        return out, jnp.sum(kl)

    split = lambda a: a.reshape((t // qb, qb) + a.shape[1:])  # noqa: E731
    out, kl = jax.lax.map(
        block, (split(q), split(qi), split(wi), jnp.arange(0, t, qb)))
    return out.reshape(q.shape), jnp.sum(kl) / t


def experts(m, r, p, cfg, precision):
    """The held experts' part of the layer's result for every token."""
    top, chosen = jax.lax.top_k(r, cfg["num_experts_per_tok"])
    weight = jax.nn.softmax(top, axis=-1)
    y = jnp.zeros_like(m)
    for i, e in enumerate(cfg["model"]["experts_held"]):
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        hidden = jax.nn.silu(matmul(m, p["experts_gate"][i], precision)) \
            * matmul(m, p["experts_up"][i], precision)
        y = y + w_e[:, None] * matmul(hidden, p["experts_down"][i], precision)
    return y


def layer(x, p, cfg, precision):
    """(the layer's output, its L_I)."""
    t = x.shape[0]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    sa = cfg["sa_config"]
    a = rms_norm(x, p["norm_attn"]["scale"], eps)
    q = matmul(a, p["q"]["kernel"], precision).reshape(t, heads, -1)
    k = matmul(a, p["k"]["kernel"], precision).reshape(t, kv, -1)
    v = matmul(a, p["v"]["kernel"], precision).reshape(t, kv, -1)
    q = rotary(rms_norm(q, p["q_norm"]["scale"], eps), theta)
    k = rotary(rms_norm(k, p["k_norm"]["scale"], eps), theta)
    # the indexer: float32, from the layer's normed input as a constant
    d = jax.lax.stop_gradient(a)
    qi = (d @ p["index_q"]["kernel"]).reshape(
        t, sa["indexer_num_heads"], sa["indexer_head_dim"])
    ki = layer_norm(d @ p["index_k"]["kernel"], p["index_k_norm"]["scale"],
                    p["index_k_norm"]["bias"], eps)
    wi = d @ p["index_w"]["kernel"]
    qi, ki = rotary(qi, theta), rotary(ki[:, None], theta)[:, 0]
    o, index_loss = attention(
        q.reshape(t, kv, heads // kv, -1), k, v, qi, ki, wi, sa["topk"])
    x = x + matmul(o.reshape(t, -1), p["o"]["kernel"], precision)
    m = rms_norm(x, p["norm_moe"]["scale"], eps)
    r = m @ p["router"]["kernel"]  # float32, whatever the control rounds
    return x + experts(m, r, p, cfg, precision), index_loss


def loss_terms(params, tokens, targets, cfg, precision):
    """One sequence: (L_lm, [L_I of each layer])."""
    x = params["embed"]["embedding"][tokens]
    index_losses = []
    for i in range(cfg["num_hidden_layers"]):
        x, index_loss = jax.checkpoint(
            lambda x, p: layer(x, p, cfg, precision)
        )(x, params[f"MoEDecoderBlock_{i}"])
        index_losses.append(index_loss)
    logits = matmul(
        rms_norm(x, params["norm"]["scale"], cfg["rms_norm_eps"]),
        params["head"]["kernel"], precision)
    logp = jax.nn.log_softmax(logits)
    lm = -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))
    return lm, jnp.stack(index_losses)


def loss_fn(params, tokens, targets, cfg, precision):
    lm, index_losses = loss_terms(params, tokens, targets, cfg, precision)
    return lm + jnp.sum(index_losses)


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
