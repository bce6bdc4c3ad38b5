"""Plain float32 Brumby-14B-Base, one chip's share (sizes from
manifestai/Brumby-14B-Base ``config.json``; the cut and every reading the
config leaves open are in ``configs/brumby-14b.json``): Qwen3-14B's skeleton
with **power retention** of degree 2 (Buckman, Gelada, Zhang,
arXiv:2507.04239) where softmax attention stood, in every layer.

One layer, input ``x`` ``[t, D]``, this chip holding ``num_key_value_heads``
KV heads with their query heads and gates and ``model.dense_columns_held``
columns of the feed-forward; ``d = head_dim``: ``u = RMSNorm(x)``. ``q_n =
rot(RMSNorm_d((u W_q)_n))``, ``k_h = rot(RMSNorm_d((u W_k)_h))``, each head's
norm with one learned scale of ``d`` for all the heads, rotary over the
whole head (its halves against each other, theta ``rope_theta``, positions
from 0); ``v_h = (u W_v)_h``; no bias. ``g_h = log sigmoid(u . w_h + b_h)``,
one number a position and KV head. For ``j <= i``, query head ``n`` reading
KV head ``h = n // group``: ``a_ij = exp(sum_{l = j+1..i} g_l,h) (q_i,n .
k_j,h / sqrt(d))^2``, else 0; ``y_i,n = sum_j a_ij v_j,h / (sum_j a_ij +
eps)``. ``x' = x + concat(y) W_o``; ``r = RMSNorm(x')``; ``out = x' + (silu(r
W_g) * (r W_u)) W_d`` over the held columns. The model: the embedding's
rows; the layers; RMSNorm; logits ``x W_head`` over the vocabulary's slice;
mean next-token cross-entropy. What the heads and columns held elsewhere
would add is left out.

Nothing of the program is imported, and retention is **in its attention
form**, ``a_ij`` over every causal pair (not the chunked recurrence the
program runs, nor any state): a block of queries at a time against all the
keys under a mask. The decay's exponent is summed outward from the block,
never a difference of two long sums: ``sum_{l = j+1..i} g_l = c_i - c_j +
R_j`` with ``c`` the running sum from the block's first position (zero
before it) and ``R_j`` the sum from ``j + 1`` to the position before the
block (zero inside it), so the exponent's rounding is relative to its own
size. The loss runs a block of rows at a time; the gradient is accumulated
a sequence at a time and each layer is recomputed in backward, so that
float32 fits the chip; rows do not interact, so that changes no number.
"""

import math

import jax
import jax.numpy as jnp

from benchmark import reference as ref

QUERY_BLOCK = 512
ROW_BLOCK = 1024


def _block_of(t, block):
    return block if t % block == 0 else t


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


def retention(q, k, v, log_g, eps, precision):
    """The attention form. q: [t, kv_heads, group, d]; k, v: [t, kv_heads,
    d]; log_g: [t, kv_heads]. A block of queries at a time, each recomputed
    in backward."""
    t, d = q.shape[0], q.shape[-1]
    qb = _block_of(t, QUERY_BLOCK)
    pos = jnp.arange(t)
    k_, v_ = ref.operand(k, precision), ref.operand(v, precision)

    @jax.checkpoint
    def block(args):
        qs, start = args
        before = (pos < start)[:, None]
        fed = jnp.where(before, log_g, 0.0)
        # R_j: from j + 1 to the position before the block
        r = jnp.cumsum(fed[::-1], axis=0)[::-1] - fed
        # c_j: from the block's first position to j
        c = jnp.cumsum(jnp.where(before, 0.0, log_g), axis=0)
        c_i = jax.lax.dynamic_slice_in_dim(c, start, qb)
        seen = pos[None, :] <= start + jnp.arange(qb)[:, None]   # [i, j]
        exponent = c_i.T[:, :, None] + (r - c).T[:, None, :]     # [h, i, j]
        decay = jnp.exp(jnp.where(seen, exponent, -jnp.inf))
        s = jnp.einsum("ihgd,jhd->hgij", ref.operand(qs, precision),
                       k_) / math.sqrt(d)
        a = jnp.square(s) * decay[:, None]
        num = jnp.einsum("hgij,jhd->ihgd", ref.operand(a, precision), v_)
        den = jnp.sum(a, axis=-1).transpose(2, 0, 1)[..., None]
        return num / (den + eps)

    out = jax.lax.map(
        block, (q.reshape((t // qb, qb) + q.shape[1:]),
                jnp.arange(0, t, qb)))
    return out.reshape(q.shape)


def retention_part(u, p, cfg, precision):
    """What the held heads add to the residual stream, from the normed
    input ``u``."""
    t, kv = u.shape[0], cfg["num_key_value_heads"]
    d, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    heads = cfg["num_attention_heads"]
    theta = float(cfg["rope_theta"])
    q = matmul(u, p["q"]["kernel"], precision).reshape(t, heads, d)
    k = matmul(u, p["k"]["kernel"], precision).reshape(t, kv, d)
    v = matmul(u, p["v"]["kernel"], precision).reshape(t, kv, d)
    q = rotary(rms_norm(q, p["q_norm"]["scale"], eps), theta)
    k = rotary(rms_norm(k, p["k_norm"]["scale"], eps), theta)
    log_g = jax.nn.log_sigmoid(
        matmul(u, p["gate"]["kernel"], precision) + p["gate"]["bias"])
    y = retention(q.reshape(t, kv, heads // kv, d), k, v, log_g,
                  cfg["model"]["retention_eps"], precision)
    return matmul(y.reshape(t, -1), p["o"]["kernel"], precision)


def feed_forward_part(h, p, cfg, precision):
    """What the held columns of the feed-forward add."""
    r = rms_norm(h, p["norm_mlp"]["scale"], cfg["rms_norm_eps"])
    hidden = jax.nn.silu(
        matmul(r, p["mlp_gate"]["kernel"], precision)
    ) * matmul(r, p["mlp_up"]["kernel"], precision)
    return matmul(hidden, p["mlp_down"]["kernel"], precision)


def layer(h, p, cfg, precision):
    u = rms_norm(h, p["norm_ret"]["scale"], cfg["rms_norm_eps"])
    h = h + retention_part(u, p, cfg, precision)
    return h + feed_forward_part(h, p, cfg, precision)


def head_loss(x, w, targets, precision):
    """Mean cross-entropy of ``x w``, a block of rows at a time, each
    recomputed in backward."""
    t = x.shape[0]
    rows = _block_of(t, ROW_BLOCK)
    x, w = ref.operand(x, precision), ref.operand(w, precision)

    @jax.checkpoint
    def block(args):
        xs, ys = args
        logp = jax.nn.log_softmax(xs @ w)
        return -jnp.sum(jnp.take_along_axis(logp, ys[:, None], axis=-1))

    return jnp.sum(jax.lax.map(block, (
        x.reshape(t // rows, rows, -1), targets.reshape(t // rows, rows)
    ))) / t


def loss_fn(params, tokens, targets, cfg, precision):
    """One sequence: tokens and targets are [t]."""
    h = params["embed"]["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        h = jax.checkpoint(lambda h, p: layer(h, p, cfg, precision))(
            h, params[f"RetentionDecoderBlock_{i}"])
    return head_loss(
        rms_norm(h, params["norm"]["scale"], cfg["rms_norm_eps"]),
        params["head"]["kernel"], targets, precision)


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
