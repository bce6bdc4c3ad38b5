"""What every language model here calls and no one of them owns: the
recomputation of a block (``recomputed``), the rotation of a whole head
(``rotary``), the engine's loss function of a model that keeps no state
(``make_lm_loss_fn``), the parameters of one (``init_lm_params``), and the
loss written plainly (``lm_cross_entropy``: no program path calls it since
the head makes its loss itself, ``lm_head.VocabHead``; it is the reference
``tests/test_lm_head.py`` and ``scripts/lm_head_probe.py`` hold the head
to). The models' files import from here and not from each other."""

from __future__ import annotations

import flax.linen as fnn
import jax
import jax.numpy as jnp

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
