"""Plain float32 LFM2-8B-A1B, one chip's share (sizes from
LiquidAI/LFM2-8B-A1B ``config.json``; the cut and every reading the config
leaves open are in ``configs/lfm2-8b-a1b.json``).

Stream ``h`` ``[t, 2048]``, no bias anywhere; ``RMSNorm(x; w) = x /
sqrt(mean(x^2) + 1e-5) * w``. Layer ``l`` of kind ``layer_types[l]``: ``a =
RMSNorm(h; w_operator)``.

- **conv**: ``[B | C | x] = a W_in`` (three column blocks of 2,048 in that
  order); ``u = B * x``; ``v_t = sum_{j < 3} k_j u_{t - 2 + j}`` a channel,
  zeros before position 0; ``y = C * v``; ``h' = h + y W_out``.
- **full_attention**: ``q = a W_q`` (32 heads of 64), ``k = a W_k``, ``v = a
  W_v`` (8 heads); each query and key head through an RMSNorm of its own
  (``w`` ``[64]``); both rotated over the whole head, halves against each
  other, theta 1e6, positions from 0; key ``j`` seen from query ``i`` iff ``j
  <= i``; ``o_n = softmax(q_n . k_{n // 4} / 8) v_{n // 4}``; ``h' = h +
  concat(o) W_o``.

``m = RMSNorm(h'; w_ffn)``. The first ``num_dense_layers`` layers: ``out =
h' + (silu(m W_1) * (m W_3)) W_2``. The others: ``s = sigmoid(m W_r)`` over
all 32; the token's ``chosen`` are the 4 largest of ``s + b``; ``w_e = s_e /
(sum_chosen s + 1e-6)`` times ``routed_scaling_factor``; ``out = h' + sum
over the chosen experts HELD HERE of w_e (silu(m W_1^e) * (m W_3^e)) W_2^e``.
What the experts held elsewhere would add is left out. After the last layer
``logits = RMSNorm(h; w_embedding_norm) E^T`` with ``E`` the table the
tokens were looked up in; mean next-token cross-entropy; no auxiliary loss.

**The bias**, once a step, outside the gradient, from 0: ``c_e`` the tokens
of the step's whole batch whose ``chosen`` hold ``e``, over all 32; ``b_e <-
b_e + u sign(mean(c) - c_e)``. The step after reads it.

Nothing of the program is imported. Attention runs a block of queries at a
time against all the keys under a mask; the experts are a loop over those
held, each over every token, under a mask: no sort, no grouped product; the
loss a block of rows at a time. The gradient is accumulated a sequence at a
time and each layer is recomputed in backward (inside it the mixer, the
feed-forward and each expert by themselves), so that float32 fits the chip
beside the weights, their copy, the two moments and the gradient (9.5 GiB);
rows do not interact, so that changes no number.

For the harness's comparison of the model state leaf by leaf (``follow``'s
``stat_norms``): each router's bias after the followed steps, and of the
last step the routes the bias turned, the tokens each held expert received
and the rows the PROGRAM's grouped products ran over. The last is no
mathematics of the model: ``tier_rows`` restates the program's documented
two-tier rule so that the tree has the program's leaves.
"""

import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

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
    """x: [t, heads, d]; the whole head rotated, positions 0 .. t - 1."""
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


def conv(u, kernel, precision):
    """u: [t, channels]; kernel: [taps, channels]; no bias."""
    t, taps = u.shape[0], kernel.shape[0]
    u, kernel = ref.operand(u, precision), ref.operand(kernel, precision)
    v = 0.0
    for j in range(taps):
        back = taps - 1 - j  # tap j meets the position ``back`` before
        v = v + kernel[j] * jnp.concatenate(
            [jnp.zeros_like(u[:back]), u[:t - back]])
    return v


def conv_part(a, p, cfg, precision):
    """What the gated short convolution adds, from the normed input."""
    gate_in, gate_out, x = jnp.split(
        matmul(a, p["in_proj"]["kernel"], precision), 3, axis=-1)
    y = gate_out * conv(gate_in * x, p["conv_kernel"], precision)
    return matmul(y, p["out_proj"]["kernel"], precision)


def attention_part(a, p, cfg, precision):
    """What the grouped softmax attention adds, from the normed input."""
    t, kv = a.shape[0], cfg["num_key_value_heads"]
    heads, eps = cfg["num_attention_heads"], cfg["norm_eps"]
    theta = float(cfg["rope_theta"])
    q = matmul(a, p["q"]["kernel"], precision).reshape(t, heads, -1)
    k = matmul(a, p["k"]["kernel"], precision).reshape(t, kv, -1)
    v = matmul(a, p["v"]["kernel"], precision).reshape(t, kv, -1)
    q = rotary(rms_norm(q, p["q_norm"]["scale"], eps), theta)
    k = rotary(rms_norm(k, p["k_norm"]["scale"], eps), theta)
    o = attention(q.reshape(t, kv, heads // kv, -1), k, v)
    return matmul(o.reshape(t, -1), p["o"]["kernel"], precision)


def gated(m, w_gate, w_up, w_down, precision):
    hidden = jax.nn.silu(matmul(m, w_gate, precision)) \
        * matmul(m, w_up, precision)
    return matmul(hidden, w_down, precision)


def route(r, bias, cfg):
    """(weight [t, k], chosen [t, k], the tokens that chose each of ALL the
    experts [E], the routes the bias turned) from the router's logits ``r``
    ``[t, E]`` and its bias ``[E]``."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(r)
    _, chosen = jax.lax.top_k(s + bias, k)
    top = jnp.take_along_axis(s, chosen, axis=-1)
    weight = cfg["routed_scaling_factor"] * top / (
        jnp.sum(top, axis=-1, keepdims=True) + cfg["model"]["route_epsilon"])
    counts = jnp.sum(jax.nn.one_hot(chosen, r.shape[-1]), axis=(0, 1))
    bare = jax.lax.top_k(s, k)[0][:, -1:]
    return weight, chosen, counts, jnp.sum(top < bare)


def experts(m, weight, chosen, p, cfg, precision):
    """The held routed experts' part of the layer's result for every token:
    a loop over the experts held, each over every token under its mask, each
    made again in backward."""
    one = jax.checkpoint(
        lambda w_e, *w: w_e[:, None] * gated(m, *w, precision))
    y = jnp.zeros_like(m)
    for i, e in enumerate(cfg["model"]["experts_held"]):
        y = y + one(
            jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1),
            p["experts_gate"][i], p["experts_up"][i], p["experts_down"][i])
    return y


def feed_forward_part(h, p, bias, cfg, sparse, precision):
    """(what this chip's feed-forward adds, (counts [E], routes turned)):
    the dense layer whole, or the held routed experts."""
    m = rms_norm(h, p["norm_moe"]["scale"], cfg["norm_eps"])
    if not sparse:
        return gated(m, p["mlp_gate"]["kernel"], p["mlp_up"]["kernel"],
                     p["mlp_down"]["kernel"], precision), None
    r = m @ p["router"]["kernel"]  # float32, whatever the control rounds
    weight, chosen, counts, turned = route(r, bias, cfg)
    return (experts(m, weight, chosen, p, cfg, precision),
            jax.lax.stop_gradient((counts, turned)))


def layer(h, p, bias, cfg, kind, sparse, precision):
    """The mixer's part and the feed-forward's each made again in backward
    by themselves."""
    def mixer_part(h, p):
        a = rms_norm(h, p["norm_attn"]["scale"], cfg["norm_eps"])
        return (conv_part if kind == "conv" else attention_part)(
            a, p, cfg, precision)

    h = h + jax.checkpoint(mixer_part)(h, p)
    add, measured = jax.checkpoint(
        lambda h, p: feed_forward_part(h, p, bias, cfg, sparse, precision)
    )(h, p)
    return h + add, measured


def head_loss(x, table, targets, precision):
    """Mean cross-entropy of ``x table^T``, a block of rows at a time, each
    recomputed in backward."""
    t = x.shape[0]
    rows = _block_of(t, ROW_BLOCK)
    x, w = ref.operand(x, precision), ref.operand(table, precision).T

    @jax.checkpoint
    def block(args):
        xs, ys = args
        logp = jax.nn.log_softmax(xs @ w)
        return -jnp.sum(jnp.take_along_axis(logp, ys[:, None], axis=-1))

    return jnp.sum(jax.lax.map(block, (
        x.reshape(t // rows, rows, -1), targets.reshape(t // rows, rows)
    ))) / t


def loss_fn(params, biases, tokens, targets, cfg, precision):
    """One sequence: tokens and targets are [t]; ``biases`` one [E] an
    expert layer. (loss, (counts [expert layers, E], routes turned [expert
    layers]))."""
    dense = cfg["num_dense_layers"]
    h = params["embed"]["embedding"][tokens]
    measured = []
    for i, kind in enumerate(cfg["layer_types"]):
        sparse = i >= dense
        h, got = jax.checkpoint(
            lambda h, p, bias, kind=kind, sparse=sparse:
            layer(h, p, bias, cfg, kind, sparse, precision)
        )(h, params[f"MoEDecoderBlock_{i}"],
          biases[i - dense] if sparse else None)
        if sparse:
            measured.append(got)
    counts, turned = (jnp.stack(a) for a in zip(*measured))
    return head_loss(
        rms_norm(h, params["norm"]["scale"], cfg["norm_eps"]),
        params["embed"]["embedding"], targets, precision), (counts, turned)


def tier_rows(routes, held_routes, held, experts):
    """The rows the PROGRAM's grouped products run over in a layer of
    ``routes`` routes of which ``held_routes`` reach the ``held`` of
    ``experts`` held here (``parallel/ep.py`` ``moe_local_experts``, as its
    docstring states it): a compact tier of twice the expected share in
    tiles of 512 rows where that is fewer than the routes and the held
    routes fit it, else every route."""
    tier = min(routes, -(-2 * routes * held // (experts * 512)) * 512)
    return tier if held_routes <= tier else routes


def follow(cfg, params, batches, groups=1, precision="float32",
           moment_after=1):
    """``groups``: the chips that divide a batch among them, each bringing
    its own sequences to the same experts: a step's counts are summed over
    all of them before they move the bias, and what a chip's state shows of
    its own routing (the held experts' tokens, the rows, the routes turned)
    is the chips' mean, as the engine leaves it."""
    # what the timed step left behind goes before this needs the memory
    gc.collect()
    m = cfg["model"]
    held, outputs = list(m["experts_held"]), m["router_outputs"]
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    state = {"bias": [jnp.zeros((outputs,), jnp.float32)] * layers}
    with jax.default_matmul_precision("highest"):
        grad_row = jax.jit(jax.value_and_grad(
            lambda p, b, x, y: loss_fn(p, b, x, y, cfg, precision),
            has_aux=True))
        add = jax.jit(
            lambda acc, g: jax.tree_util.tree_map(jnp.add, acc, g),
            donate_argnums=(0,))
        scale = jax.jit(
            lambda acc, n: jax.tree_util.tree_map(lambda a: a / n, acc),
            donate_argnums=(0,))

        def loss_and_grad(params, x, y):
            acc, total, counts, turned = None, 0.0, [], []
            for i in range(len(x)):
                (loss, (c, t)), g = grad_row(
                    params, state["bias"], jnp.asarray(x[i]),
                    jnp.asarray(y[i]))
                acc = g if acc is None else add(acc, g)
                total += float(loss)
                counts.append(np.asarray(c, np.float64))
                turned.append(np.asarray(t, np.float64))
                del g
            # [chips, sequences a chip, ...] summed over a chip's sequences
            counts = np.stack(counts).reshape(
                (groups, -1) + counts[0].shape).sum(axis=1)
            turned = np.stack(turned).reshape(groups, -1, layers).sum(axis=1)
            routes = (len(x) // groups) * x.shape[1] * cfg[
                "num_experts_per_tok"]
            load = counts[:, :, held]
            state["load"] = load.mean(axis=0)
            state["turned"] = turned.mean(axis=0)
            state["rows"] = np.mean([[
                tier_rows(routes, n, len(held), outputs)
                for n in chip.sum(axis=-1)] for chip in load], axis=0)
            whole = counts.sum(axis=0)
            state["bias"] = [
                b + jnp.asarray(m["bias_update_rate"] * np.sign(
                    c.mean() - c), jnp.float32)
                for b, c in zip(state["bias"], whole)]
            return total / len(x), scale(acc, float(len(x)))

        got = ref.follow(cfg, loss_and_grad, params, batches, moment_after)
    # the model state as the program's engine would hold it after the last
    # step; every entry starts from 0, so its change's norm is its norm
    got["model_state"] = {
        "moe_bias": [np.asarray(b) for b in state["bias"]],
        "moe_biased_routes": state["turned"],
        "moe_load": state["load"],
        "moe_rows": state["rows"],
    }
    got["stat_norms"] = jax.tree_util.tree_map(
        lambda a: float(np.sqrt(np.sum(np.square(
            np.asarray(a, np.float64))))), got["model_state"])
    return got
