"""Plain float32 Falcon-H1-34B-Instruct, one chip's share (sizes from
tiiuae/Falcon-H1-34B-Instruct ``config.json``; the cut and every reading the
config leaves open are in ``configs/falcon-h1-34b.json``).

One layer, input ``h`` ``[t, d]``, this chip holding ``num_key_value_heads``
KV heads with their query heads, ``mamba_n_heads`` mixer heads of
``mamba_d_head`` in ``mamba_n_groups`` groups whole, and
``model.dense_columns_held`` columns of the feed-forward; ``m`` =
``ssm_multipliers``: ``a = RMSNorm(h)``.

Mixer: ``[z | x | B | C | dt] = (ssm_in_multiplier a) W_in``, the five parts
times ``m[0..4]``. ``[x | B | C] <- silu(conv(x | B | C))``: ``conv(u)_t =
bias + sum_{j < 4} w_j u_{t - 3 + j}`` a channel, zeros before position 0.
``delta_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``, one number a
head. A head's state ``S_t = exp(delta_t A) S_{t-1} + (delta_t x_t) (x)
B_t`` (``[d_head, d_state]``, ``S_{-1} = 0``, ``B`` and ``C`` its group's);
``y_t = S_t C_t + D x_t``. ``g = y * silu(z)``; over each group's channels
HELD HERE ``g <- g / sqrt(mean(g^2) + eps)`` times a weight; ``mix =
ssm_out_multiplier (g W_out)``.

Attention: ``a' = attention_in_multiplier a``; ``q = a' W_q``, ``k =
key_multiplier (a' W_k)``, ``v = a' W_v``; rotary over the whole head (its
halves against each other, theta ``rope_theta``, positions from 0); key
``j`` seen from query ``i`` iff ``j <= i``; ``o_n = softmax(q_n . k /
sqrt(head_dim)) v``; ``att = attention_out_multiplier (concat(o) W_o)``.

``h' = h + mix + att``; ``f = RMSNorm(h')``; ``out = h' + mlp_multipliers[1]
((silu(mlp_multipliers[0] (f W_g)) * (f W_u)) W_d)`` over the held columns.
The model: the embedding's rows times ``embedding_multiplier``; the layers;
RMSNorm; logits ``lm_head_multiplier (x W_head)`` over the vocabulary's
slice; mean next-token cross-entropy. What the heads and columns held
elsewhere would add is left out.

Nothing of the program is imported, and the scan is **the recurrence
itself**, a ``lax.scan`` over time (not the chunked dual the program runs),
checkpointed by stretches so that backward fits. Attention runs a block of
queries at a time against all the keys under a mask, the loss a block of
rows at a time; the gradient is accumulated a sequence at a time and each
layer is recomputed in backward, so that float32 fits the chip; rows do not
interact, so that changes no number.
"""

import math

import jax
import jax.numpy as jnp

from benchmark import reference as ref

QUERY_BLOCK = 512
ROW_BLOCK = 1024
STRETCH = 128


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


def attention(q, k, v):
    """q: [t, kv_heads, group, d]; k, v: [t, kv_heads, d]. A block of
    queries at a time, each recomputed in backward."""
    t = q.shape[0]
    qb = _block_of(t, QUERY_BLOCK)
    key_pos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(args):
        qs, start = args
        seen = key_pos <= start + jnp.arange(qb)[:, None]
        s = jnp.einsum("qhgd,khd->hgqk", qs, k) / math.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    out = jax.lax.map(
        block, (q.reshape((t // qb, qb) + q.shape[1:]),
                jnp.arange(0, t, qb)))
    return out.reshape(q.shape)


def conv(u, kernel, bias, precision):
    """u: [t, c]; kernel: [k, c]; bias: [c]."""
    t, taps = u.shape[0], kernel.shape[0]
    u, kernel = ref.operand(u, precision), ref.operand(kernel, precision)
    y = bias
    for j in range(taps):
        back = taps - 1 - j  # tap j meets the position ``back`` before
        y = y + kernel[j] * jnp.concatenate(
            [jnp.zeros_like(u[:back]), u[:t - back]])
    return y


def selective_scan(x, delta, a, b, c, d, precision):
    """The recurrence, a step a position. x: [t, heads, d_head]; delta:
    [t, heads]; a, d: [heads]; b, c: [t, groups, d_state]."""
    t, heads = delta.shape
    per_group = heads // b.shape[1]

    def step(state, now):
        x_t, delta_t, b_t, c_t = now
        b_t = jnp.repeat(b_t, per_group, axis=0)  # its group's, a head
        c_t = jnp.repeat(c_t, per_group, axis=0)
        fed = ref.operand(delta_t[:, None] * x_t, precision)[:, :, None] \
            * ref.operand(b_t, precision)[:, None, :]
        state = jnp.exp(delta_t * a)[:, None, None] * state + fed
        y_t = jnp.einsum("hpn,hn->hp", ref.operand(state, precision),
                         ref.operand(c_t, precision))
        return state, y_t + d[:, None] * x_t

    stretch = _block_of(t, STRETCH)

    @jax.checkpoint
    def run(state, nows):
        return jax.lax.scan(step, state, nows)

    _, y = jax.lax.scan(
        run, jnp.zeros(x.shape[1:] + b.shape[-1:], jnp.float32),
        tuple(v.reshape((t // stretch, stretch) + v.shape[1:])
              for v in (x, delta, b, c)))
    return y.reshape(x.shape)


def mixer_part(a, p, cfg, precision):
    """What the held mixer heads add to the residual stream, from the
    normed input ``a``."""
    t = a.shape[0]
    heads, groups = cfg["mamba_n_heads"], cfg["mamba_n_groups"]
    inner, bc = heads * cfg["mamba_d_head"], groups * cfg["mamba_d_state"]
    m = cfg["ssm_multipliers"]
    proj = matmul(cfg["ssm_in_multiplier"] * a, p["ssm_in"]["kernel"],
                  precision)
    z = m[0] * proj[:, :inner]
    x = m[1] * proj[:, inner:2 * inner]
    b = m[2] * proj[:, 2 * inner:2 * inner + bc]
    c = m[3] * proj[:, 2 * inner + bc:2 * inner + 2 * bc]
    dt = m[4] * proj[:, 2 * inner + 2 * bc:]
    xbc = jax.nn.silu(conv(jnp.concatenate([x, b, c], axis=-1),
                           p["conv_kernel"], p["conv_bias"], precision))
    x, b, c = xbc[:, :inner], xbc[:, inner:inner + bc], xbc[:, inner + bc:]
    y = selective_scan(
        x.reshape(t, heads, -1), jax.nn.softplus(dt + p["dt_bias"]),
        -jnp.exp(p["A_log"]), b.reshape(t, groups, -1),
        c.reshape(t, groups, -1), p["D"], precision)
    g = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, groups, -1)
    # the mean over the channels of the group that are held here
    g = g * jax.lax.rsqrt(
        jnp.mean(jnp.square(g), axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    return cfg["ssm_out_multiplier"] * matmul(
        g.reshape(t, inner) * p["ssm_norm"], p["ssm_out"]["kernel"],
        precision)


def attention_part(a, p, cfg, precision):
    """What the held heads add to the residual stream, from the normed
    input ``a``."""
    t, kv = a.shape[0], cfg["num_key_value_heads"]
    a = cfg["attention_in_multiplier"] * a
    q = matmul(a, p["q"]["kernel"], precision)
    heads = q.shape[-1] // cfg["head_dim"]
    k = cfg["key_multiplier"] * matmul(a, p["k"]["kernel"], precision)
    v = matmul(a, p["v"]["kernel"], precision).reshape(t, kv, -1)
    theta = float(cfg["rope_theta"])
    q = rotary(q.reshape(t, heads, -1), theta)
    k = rotary(k.reshape(t, kv, -1), theta)
    o = attention(q.reshape(t, kv, heads // kv, -1), k, v)
    return cfg["attention_out_multiplier"] * matmul(
        o.reshape(t, -1), p["o"]["kernel"], precision)


def feed_forward_part(h, p, cfg, precision):
    """What the held columns of the feed-forward add."""
    gate_by, down_by = cfg["mlp_multipliers"]
    f = rms_norm(h, p["norm_mlp"]["scale"], cfg["rms_norm_eps"])
    hidden = jax.nn.silu(
        gate_by * matmul(f, p["mlp_gate"]["kernel"], precision)
    ) * matmul(f, p["mlp_up"]["kernel"], precision)
    return down_by * matmul(hidden, p["mlp_down"]["kernel"], precision)


def layer(h, p, cfg, precision):
    a = rms_norm(h, p["norm_mix"]["scale"], cfg["rms_norm_eps"])
    h = h + mixer_part(a, p, cfg, precision) \
        + attention_part(a, p, cfg, precision)
    return h + feed_forward_part(h, p, cfg, precision)


def head_loss(x, w, targets, cfg, precision):
    """Mean cross-entropy of ``lm_head_multiplier (x w)``, a block of rows
    at a time, each recomputed in backward."""
    t = x.shape[0]
    rows = _block_of(t, ROW_BLOCK)
    x, w = ref.operand(x, precision), ref.operand(w, precision)

    @jax.checkpoint
    def block(args):
        xs, ys = args
        logp = jax.nn.log_softmax(cfg["lm_head_multiplier"] * (xs @ w))
        return -jnp.sum(jnp.take_along_axis(logp, ys[:, None], axis=-1))

    return jnp.sum(jax.lax.map(block, (
        x.reshape(t // rows, rows, -1), targets.reshape(t // rows, rows)
    ))) / t


def loss_fn(params, tokens, targets, cfg, precision):
    """One sequence: tokens and targets are [t]."""
    h = cfg["embedding_multiplier"] * params["embed"]["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        h = jax.checkpoint(lambda h, p: layer(h, p, cfg, precision))(
            h, params[f"HybridDecoderBlock_{i}"])
    return head_loss(
        rms_norm(h, params["norm"]["scale"], cfg["rms_norm_eps"]),
        params["head"]["kernel"], targets, cfg, precision)


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
