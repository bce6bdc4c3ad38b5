"""What every language model here calls and no one of them owns: the
recomputation of a block (``recomputed``), the rotation of a whole head
(``rotary``), the sparse feed-forward half of a block
(``sparse_feed_forward``), the seeded decay of a gated recurrence
(``a_log_init``, ``dt_bias_init``), the engine's loss function of a model
that keeps no state (``make_lm_loss_fn``), the parameters of one (``init_lm_params``),
and the loss written plainly (``lm_cross_entropy``: no program path calls
it since the head makes its loss itself, ``lm_head.VocabHead``; it is the
reference ``tests/test_lm_head.py`` and ``scripts/lm_head_probe.py`` hold
the head to). The models' files import from here and not from each
other."""

from __future__ import annotations

import math
from typing import Callable, Optional, Sequence

import flax.linen as fnn
import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.ep import moe_local_experts, softmax_route_weights
from ..parallel.ring_attention import SAVED as _ATTN_SAVED
from ..telemetry import names as _names


def lm_cross_entropy(logits, targets):
    """Mean next-token cross-entropy over every position, the log-softmax
    in float32: the one loss of every language model here."""
    with jax.named_scope(_names.SCOPE_LM_LOSS):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -jnp.mean(picked)


def recomputed(block_cls):
    """``block_cls`` recomputed in backward, but for what its attention
    call's forward kernels hand to their backward kernels: the arrays that
    bear the ``checkpoint_name`` ``ring_attention.SAVED``
    (``blocked_self_attention``'s output and log-sum-exp where it takes the
    fused kernels; a selecting layer's output, log-sum-exps, thresholds and
    panels of index scores) are kept, so an attention kernel runs forward
    once a step and not again with the block; the rest of the block is made
    again. Where the call takes the loops nothing bears the name, and the
    whole block is recomputed. The one spelling of every model's ``remat``."""
    return fnn.remat(
        block_cls,
        policy=jax.checkpoint_policies.save_only_these_names(_ATTN_SAVED))


def make_lm_loss_fn(model: fnn.Module):
    """Next-token loss for the engine: ``loss_fn(params, batch)`` with
    ``batch = (tokens_in, tokens_target)``, both ``[B, T]`` int32. Mean
    cross-entropy over every position (the engine's batch contract matches
    ``models.mnist.make_loss_fn`` so LMs drive the same train loops the
    classifiers do). ``model(tokens, targets)`` is that loss."""

    def loss_fn(params, batch):
        tokens, targets = batch
        # the model's head makes the loss itself (``lm_head.VocabHead``): no
        # logits between the two
        return model.apply({"params": params}, tokens, targets)

    return loss_fn


def init_lm_params(model: fnn.Module, seq_len: int, seed: int = 0):
    rng = jax.random.PRNGKey(seed)
    variables = model.init(rng, jnp.zeros((1, seq_len), jnp.int32))
    return variables["params"]


def rotary(x, theta: float):
    """Rotary position over the whole head of ``x`` ``[b, t, h, d]``, its
    halves rotated against each other, positions ``0 .. t - 1``; float32
    inside, ``x``'s dtype out."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def a_log_init(key, shape, dtype=jnp.float32):
    """``A`` uniform in [1, 16] (Mamba-2's published initialisation; the
    gated delta rule's gate copies its parametrisation, ``g = -A softplus(a
    + dt_bias)``)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias_init(key, shape, dtype=jnp.float32):
    """``softplus(dt_bias)`` log-uniform in [1e-3, 1e-1], through the
    inverse of the softplus."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def sparse_feed_forward(block: fnn.Module, x, h, *, expert_width: int,
                        num_experts: int, top_k: int, held: Sequence[int],
                        activation: Callable, dtype, logits=None,
                        route_weights: Callable = softmax_route_weights,
                        shared_width: Optional[int] = None,
                        shared_sigmoid: bool = False):
    """The sparse feed-forward half of a block, called inside ``block``'s
    compact ``__call__`` (the parameters made here are ``block``'s own, under
    the names every sparse decoder's tree has): ``x + shared(h) + sum_{e
    chosen, e held} w_e expert_e(h)`` from the stream ``x`` ``[b, t, d]``
    and its normed copy ``h``, each expert a gated feed-forward ``(act(h
    W_gate) * (h W_up)) W_down``, with what each held expert received and
    the rows the grouped products ran over (``ep.moe_local_experts``).

    ``logits``: the router's ``[b, t, num_experts]`` float32 where the
    caller read it elsewhere (before attention); None: the router reads
    ``h`` here, its product float32 at precision highest. ``shared_width``:
    a shared expert of that many columns beside the routed ones, which every
    token takes at weight 1 or, with ``shared_sigmoid``, at ``sigmoid(h .
    w_s)``, one number a token (``shared_expert_gate``)."""
    b, t, d = x.shape
    dense = lambda n, name: fnn.Dense(  # noqa: E731
        n, use_bias=False, dtype=dtype, name=name)
    if logits is None:
        with jax.named_scope(_names.SCOPE_MOE_ROUTER):
            logits = fnn.Dense(
                num_experts, use_bias=False, dtype=jnp.float32,
                precision=lax.Precision.HIGHEST, name="router"
            )(h.astype(jnp.float32))
    # ``h`` is cast where it is read, twice, as ``MoEDecoderBlock`` did
    # before this was a function: its lowered steps are pinned by hash
    if shared_width is not None:
        with jax.named_scope(_names.SCOPE_MOE_SHARED):
            m = h.astype(dtype)
            shared = dense(d, "shared_down")(
                activation(dense(shared_width, "shared_gate")(m))
                * dense(shared_width, "shared_up")(m))
            if shared_sigmoid:
                shared = (shared * jax.nn.sigmoid(
                    dense(1, "shared_expert_gate")(m).astype(jnp.float32))
                ).astype(shared.dtype)
            x = x + shared
    init = fnn.initializers.lecun_normal(in_axis=-2, out_axis=-1)
    n, f = len(held), expert_width
    y, load, rows = moe_local_experts(
        h.astype(dtype).reshape(b * t, d),
        logits.reshape(b * t, num_experts), top_k,
        block.param("experts_gate", init, (n, d, f), jnp.float32),
        block.param("experts_up", init, (n, d, f), jnp.float32),
        block.param("experts_down", init, (n, f, d), jnp.float32),
        tuple(held), activation=activation, route_weights=route_weights)
    return x + y.reshape(b, t, d), load, rows
