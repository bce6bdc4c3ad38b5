"""Plain float32 Qwen3-Next-80B-A3B-Instruct, one chip's share (sizes from
Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``; the cut and every reading
the config leaves open are in ``configs/qwen3-next-80b-a3b.json``).

``zrms(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``. Layer ``l``, input ``x``
``[t, d]``, is **full** iff ``(l + 1) % full_attention_interval == 0``, else
**linear**: ``h = x + Mixer_l(zrms(x))``; ``out = h + MoE(zrms(h))``.

Linear mixer, ``u = zrms(x)``, 16 key heads ``j`` of 128, 32 value heads
``n`` of 128, ``n`` reading key head ``n // 2``: ``[q | k | v | z] = u
W_qkvz``; ``[b | a] = u W_ba``; ``[q | k | v] <- silu(conv([q | k | v]))``,
``conv(c)_t = sum_{j < 4} w_j c_{t - 3 + j}`` a channel, zeros before
position 0, no bias; ``q_j <- q_j / sqrt(sum q_j^2 + 1e-6) / sqrt(128)``,
``k_j <- k_j / sqrt(sum k_j^2 + 1e-6)``; ``beta_n = sigmoid(b_n)``, ``g_n =
-exp(A_log_n) softplus(a_n + dt_bias_n)``. A value head's state ``S`` ``[128,
128]`` from 0: ``S_t = e^{g_t} S_{t-1}``; ``r_t = S_t^T k_t``; ``u_t =
beta_t (v_t - r_t)``; ``S_t <- S_t + k_t u_t^T``; ``o_t = S_t^T q_t``. ``y_n
= o_n / sqrt(mean(o_n^2) + eps) * w_o * silu(z_n)`` (``w_o`` not
zero-centred); ``Mixer = [y_0 .. y_31] W_out``.

Full mixer, 16 query heads of 256, query head ``n`` reading KV head ``n //
8`` of 2: ``[q_n | gate_n] = (u W_q)_n``; ``k = u W_k``, ``v = u W_v``; ``q_n
<- rot(zrms_256(q_n))``, ``k_m <- rot(zrms_256(k_m))``: the first 64 of a
head rotated, halves against each other, theta ``rope_theta``, positions
from 0, the other 192 passed on; key ``j`` seen from query ``i`` iff ``j <=
i``; ``att_n = softmax(q_n . k / sqrt(256)) v``; ``Mixer = [att_n *
sigmoid(gate_n)]_n W_o``.

MoE, ``m = zrms(h)``: ``p = softmax(m W_r)`` over all 512; the token's
experts are its 10 largest ``p``, ``w_e = p_e / sum_chosen p``; ``MoE = sum
over the chosen experts HELD HERE of w_e (silu(m G_e) * (m U_e)) D_e +
sigmoid(m . w_s) (silu(m G_s) * (m U_s)) D_s``. What the experts held
elsewhere would add is left out. Then ``zrms``, the untied head over the
vocabulary's slice, mean next-token cross-entropy. No auxiliary loss, no
multi-token prediction.

Nothing of the program is imported, and the rule is **the recurrence
itself**, a ``lax.scan`` over positions (not the chunked form the program
runs), checkpointed by stretches so that backward fits. Attention runs a
block of queries at a time against all the keys under a mask; the experts
are a loop over those held, each over every token, under a mask: no sort, no
grouped product; the loss a block of rows at a time. The gradient is
accumulated a sequence at a time and each layer is recomputed in backward
(inside it the mixer, the expert layer and each expert by themselves), so
that float32 fits the chip beside the weights, their copy, the two moments
and the gradient (7.9 GiB); rows do not interact, so that changes no
number.
"""

import gc
import math

import jax
import jax.numpy as jnp

from benchmark import reference as ref

QUERY_BLOCK = 512
ROW_BLOCK = 1024
STRETCH = 128
L2_EPS = 1e-6


def _block_of(t, block):
    return block if t % block == 0 else t


def matmul(x, w, precision):
    return ref.operand(x, precision) @ ref.operand(w, precision)


def zrms(x, w, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + w)


def rotary(x, width, theta):
    """x: [t, heads, d]; the first ``width`` of a head rotated, positions
    0 .. t - 1."""
    half = width // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:width], x[..., width:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


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


def conv(c, kernel, precision):
    """c: [t, channels]; kernel: [taps, channels]; no bias."""
    t, taps = c.shape[0], kernel.shape[0]
    c, kernel = ref.operand(c, precision), ref.operand(kernel, precision)
    y = 0.0
    for j in range(taps):
        back = taps - 1 - j  # tap j meets the position ``back`` before
        y = y + kernel[j] * jnp.concatenate(
            [jnp.zeros_like(c[:back]), c[:t - back]])
    return y


def delta_rule(q, k, v, g, beta, precision):
    """The recurrence, a step a position. q, k: [t, key_heads, dk]; v: [t,
    value_heads, dv]; g, beta: [t, value_heads]."""
    t, heads = g.shape
    per_key = heads // q.shape[1]

    def step(state, now):
        q_t, k_t, v_t, g_t, beta_t = now
        q_t = ref.operand(jnp.repeat(q_t, per_key, axis=0), precision)
        k_t = ref.operand(jnp.repeat(k_t, per_key, axis=0), precision)
        state = jnp.exp(g_t)[:, None, None] * state
        read = jnp.einsum("hkv,hk->hv", ref.operand(state, precision), k_t)
        wrote = ref.operand(beta_t[:, None] * (v_t - read), precision)
        state = state + k_t[:, :, None] * wrote[:, None, :]
        return state, jnp.einsum(
            "hkv,hk->hv", ref.operand(state, precision), q_t)

    stretch = _block_of(t, STRETCH)

    @jax.checkpoint
    def run(state, nows):
        return jax.lax.scan(step, state, nows)

    _, o = jax.lax.scan(
        run, jnp.zeros((heads, q.shape[-1], v.shape[-1]), jnp.float32),
        tuple(a.reshape((t // stretch, stretch) + a.shape[1:])
              for a in (q, k, v, g, beta)))
    return o.reshape(v.shape)


def linear_part(u, p, cfg, precision):
    """What the delta mixer adds to the residual stream, from the normed
    input ``u``. What stands before the rule is made again in backward by
    itself, after the rule's own backward."""
    t = u.shape[0]
    kh, vh = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kw, vw = kh * dk, vh * dv

    @jax.checkpoint
    def heads(u, p):
        proj = matmul(u, p["in_qkvz"]["kernel"], precision)
        ba = matmul(u, p["in_ba"]["kernel"], precision)
        qkv = jax.nn.silu(conv(proj[:, :2 * kw + vw], p["conv_kernel"],
                               precision))
        unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)
        return (unit(qkv[:, :kw].reshape(t, kh, dk)) / math.sqrt(dk),
                unit(qkv[:, kw:2 * kw].reshape(t, kh, dk)),
                qkv[:, 2 * kw:].reshape(t, vh, dv),
                -jnp.exp(p["A_log"]) * jax.nn.softplus(
                    ba[:, vh:] + p["dt_bias"]),
                jax.nn.sigmoid(ba[:, :vh]),
                proj[:, 2 * kw + vw:].reshape(t, vh, dv))

    q, k, v, g, beta, z = heads(u, p)
    o = delta_rule(q, k, v, g, beta, precision)
    y = o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg["rms_norm_eps"]
    ) * p["out_norm"] * jax.nn.silu(z)
    return matmul(y.reshape(t, vw), p["out"]["kernel"], precision)


def full_part(u, p, cfg, precision):
    """What the gated softmax attention adds, from the normed input ``u``."""
    t, kv, hd = u.shape[0], cfg["num_key_value_heads"], cfg["head_dim"]
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    q_gate = matmul(u, p["q"]["kernel"], precision).reshape(t, heads, 2 * hd)
    q, gate = q_gate[..., :hd], q_gate[..., hd:]
    k = matmul(u, p["k"]["kernel"], precision).reshape(t, kv, hd)
    v = matmul(u, p["v"]["kernel"], precision).reshape(t, kv, hd)
    width = int(hd * cfg["partial_rotary_factor"])
    theta = float(cfg["rope_theta"])
    q = rotary(zrms(q, p["q_norm"]["scale"], eps), width, theta)
    k = rotary(zrms(k, p["k_norm"]["scale"], eps), width, theta)
    o = attention(q.reshape(t, kv, heads // kv, hd), k, v)
    o = o.reshape(t, heads, hd) * jax.nn.sigmoid(gate)
    return matmul(o.reshape(t, -1), p["o"]["kernel"], precision)


def experts(m, r, p, cfg, precision):
    """The held routed experts' part of the layer's result for every token,
    from the router's logits ``r`` over all the experts: a loop over the
    experts held, each over every token under its mask, each made again in
    backward."""
    top, chosen = jax.lax.top_k(
        jax.nn.softmax(r, axis=-1), cfg["num_experts_per_tok"])
    weight = top / jnp.sum(top, axis=-1, keepdims=True)

    @jax.checkpoint
    def one(w_e, w_gate, w_up, w_down):
        hidden = jax.nn.silu(matmul(m, w_gate, precision)) \
            * matmul(m, w_up, precision)
        return w_e[:, None] * matmul(hidden, w_down, precision)

    y = jnp.zeros_like(m)
    for i, e in enumerate(cfg["model"]["experts_held"]):
        y = y + one(
            jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1),
            p["experts_gate"][i], p["experts_up"][i], p["experts_down"][i])
    return y


def moe_part(h, p, cfg, precision):
    """The shared expert whole, at its gate, and the held routed experts."""
    m = zrms(h, p["norm_moe"]["scale"], cfg["rms_norm_eps"])
    r = m @ p["router"]["kernel"]  # float32, whatever the control rounds
    shared = matmul(
        jax.nn.silu(matmul(m, p["shared_gate"]["kernel"], precision))
        * matmul(m, p["shared_up"]["kernel"], precision),
        p["shared_down"]["kernel"], precision)
    gate = jax.nn.sigmoid(matmul(m, p["shared_expert_gate"]["kernel"],
                                 precision))
    return gate * shared + experts(m, r, p, cfg, precision)


def layer(h, p, cfg, linear, precision):
    """The mixer's part and the expert layer's each made again in backward
    by themselves: one's float32 intermediates are gone when the other's
    are needed."""
    def mixer_part(h, p):
        u = zrms(h, p["norm_mix"]["scale"], cfg["rms_norm_eps"])
        return (linear_part if linear else full_part)(u, p, cfg, precision)

    h = h + jax.checkpoint(mixer_part)(h, p)
    return h + jax.checkpoint(
        lambda h, p: moe_part(h, p, cfg, precision))(h, p)


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
        h = jax.checkpoint(
            lambda h, p, linear=(
                (i + 1) % cfg["full_attention_interval"] != 0):
            layer(h, p, cfg, linear, precision)
        )(h, params[f"GatedDeltaDecoderBlock_{i}"])
    return head_loss(
        zrms(h, params["norm"]["scale"], cfg["rms_norm_eps"]),
        params["head"]["kernel"], targets, precision)


def follow(cfg, params, batches, groups=1, precision="float32",
           moment_after=1):
    """``groups`` is not needed: no layer mixes sequences, so the mean over
    the global batch is the same however the chips divide it."""
    # what the timed step left behind (its engine stands in reference
    # cycles, and with it the step's program and what that holds back on the
    # chip) goes before this needs the memory
    gc.collect()
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
