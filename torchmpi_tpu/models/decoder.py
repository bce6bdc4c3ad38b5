"""A present-day sparse decoder: RMSNorm, grouped KV heads, full or
sliding-window attention by layer (rotary position or none by layer), the
router read before attention, a gated feed-forward of routed experts of
which this device holds some.

Built from a layer pattern: ``window_layout[l % period]`` says whether layer
``l`` attends within ``window`` (else over the whole causal prefix), and
``rope_layout[l % period]`` whether its queries and keys are rotated (else
the layer has no positional encoding at all). Attention is
``parallel.ring_attention.blocked_self_attention`` (no ``t x t`` tensor; on
a TPU with heads of a multiple of 128 in fused kernels, else in loops of
XLA operations: the call decides, the model sets nothing);
the experts are ``parallel.ep.moe_local_experts`` (dropless, told which of
all the experts it holds: what the others would add is left out, the part
an exchange across devices would bring). Parameters are float32, the
matrix products run in ``dtype``, the router's product, top-k and softmax
in float32.

One layer, input ``h``: ``r = h W_r`` (before the norm, before attention);
``a = RMSNorm(h)``; ``h' = h + Attn(a) W_o``; ``m = RMSNorm(h')``; ``out = h'
+ sum_{e in top_k(r), e held} softmax(r[top_k])_e (act(m W_g^e) * (m
W_u^e)) W_d^e``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import flax.linen as fnn
import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.ep import (
    moe_local_experts,
    note_expert_layers,
    note_expert_load,
)
from ..parallel.ring_attention import (
    blocked_self_attention,
    note_attention_step,
)
from ..telemetry import names as _names
from .transformer import lm_cross_entropy


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


class MoEDecoderBlock(fnn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    expert_width: int
    num_experts: int            # the router's outputs: ALL the experts
    top_k: int
    held: Sequence[int]         # ids of the experts this device holds
    window: Optional[int] = None       # None: the whole causal prefix
    rope_theta: Optional[float] = None  # None: no positional encoding
    norm_eps: float = 1e-6
    attn_block: int = 1024
    activation: Callable = jax.nn.relu
    dtype: Any = jnp.float32

    @fnn.compact
    def __call__(self, x):
        # x: [B, T, D] -> (x, (the tokens each held expert received, the
        # rows the grouped products ran over))
        b, t, d = x.shape
        dense = lambda n, name: fnn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        logits = fnn.Dense(
            self.num_experts, use_bias=False, dtype=jnp.float32,
            precision=lax.Precision.HIGHEST, name="router",
        )(x.astype(jnp.float32))

        h = fnn.RMSNorm(
            epsilon=self.norm_eps, dtype=jnp.float32, name="norm_attn")(x)
        q = dense(self.num_heads * self.head_dim, "q")(h)
        k = dense(self.num_kv_heads * self.head_dim, "k")(h)
        v = dense(self.num_kv_heads * self.head_dim, "v")(h)
        q = q.reshape(b, t, self.num_heads, self.head_dim)
        k = k.reshape(b, t, self.num_kv_heads, self.head_dim)
        v = v.reshape(b, t, self.num_kv_heads, self.head_dim)
        with jax.named_scope(
                _names.SCOPE_ATTN_FULL if self.window is None
                else _names.SCOPE_ATTN_WINDOW):
            if self.rope_theta is not None:
                q, k = rotary(q, self.rope_theta), rotary(k, self.rope_theta)
            attn = blocked_self_attention(
                q, k, v, window=self.window, block=self.attn_block)
        x = x + dense(d, "o")(attn.reshape(b, t, -1))

        h = fnn.RMSNorm(
            epsilon=self.norm_eps, dtype=jnp.float32, name="norm_moe")(x)
        n, f = len(self.held), self.expert_width
        init = fnn.initializers.lecun_normal(in_axis=-2, out_axis=-1)
        y, load, rows = moe_local_experts(
            h.astype(self.dtype).reshape(b * t, d),
            logits.reshape(b * t, self.num_experts),
            self.top_k,
            self.param("experts_gate", init, (n, d, f), jnp.float32),
            self.param("experts_up", init, (n, d, f), jnp.float32),
            self.param("experts_down", init, (n, f, d), jnp.float32),
            tuple(self.held),
            activation=self.activation,
        )
        return x + y.reshape(b, t, d), (load, rows)


class MoEDecoder(fnn.Module):
    """Decoder-only LM over ``MoEDecoderBlock``s. Returns ``(logits [B, T,
    vocab] float32, {"moe_load": [layers, held], "moe_rows": [layers]}
    float32)``: what each layer measured of its routing."""

    vocab_size: int = 256
    num_layers: int = 4
    d_model: int = 128
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 32
    expert_width: int = 64
    num_experts: int = 8
    top_k: int = 2
    held: Sequence[int] = tuple(range(8))
    window: int = 4096
    window_layout: Sequence[int] = (0, 1, 1, 1)  # 1: within ``window``
    rope_layout: Sequence[int] = (0, 1, 1, 1)    # 1: rotary position
    rope_theta: float = 1.5e6
    norm_eps: float = 1e-6
    attn_block: int = 1024
    remat: bool = False  # recompute each block in backward
    dtype: Any = jnp.float32

    @fnn.compact
    def __call__(self, tokens):
        note_expert_layers(
            tokens.size, self.top_k, self.num_layers, len(self.held))
        note_attention_step()  # each layer's call below counts itself
        x = fnn.Embed(
            self.vocab_size, self.d_model, dtype=self.dtype, name="embed"
        )(tokens)
        block_cls = fnn.remat(MoEDecoderBlock) if self.remat \
            else MoEDecoderBlock
        routing = []
        for i in range(self.num_layers):
            windowed = self.window_layout[i % len(self.window_layout)]
            rotated = self.rope_layout[i % len(self.rope_layout)]
            x, measured = block_cls(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, expert_width=self.expert_width,
                num_experts=self.num_experts, top_k=self.top_k,
                held=tuple(self.held),
                window=self.window if windowed else None,
                rope_theta=self.rope_theta if rotated else None,
                norm_eps=self.norm_eps, attn_block=self.attn_block,
                dtype=self.dtype,
                name=f"MoEDecoderBlock_{i}",  # the same with and without remat
            )(x)
            routing.append(measured)
        x = fnn.RMSNorm(
            epsilon=self.norm_eps, dtype=jnp.float32, name="norm")(x)
        logits = fnn.Dense(
            self.vocab_size, use_bias=False, dtype=jnp.float32, name="head"
        )(x)
        load, rows = (jnp.stack(a) for a in zip(*routing))
        return logits, {"moe_load": load, "moe_rows": rows}


def init_moe_state(model: MoEDecoder):
    """The model state the engine carries for ``make_moe_lm_loss_fn``: by
    layer, the tokens each held expert received in the last step, and the
    rows the layer's grouped products ran over."""
    return {
        "moe_load": jnp.zeros(
            (model.num_layers, len(model.held)), jnp.float32),
        "moe_rows": jnp.zeros((model.num_layers,), jnp.float32),
    }


def make_moe_lm_loss_fn(model: MoEDecoder):
    """Next-token loss for the engine's ``model_state`` path:
    ``loss_fn(params, state, batch) -> (loss, new state)``, the state
    being :func:`init_moe_state`'s (what the step measured of its routing
    rides the path batch norm's statistics take: no further output of the
    step). No auxiliary load-balancing loss. Where the engine reads an
    epoch's loss it hands the state to ``loss_fn.observe_state``, which
    sets ``tm_moe_held_routes_last_step``, ``tm_moe_max_over_mean_load``,
    ``tm_moe_grouped_rows_per_step`` and
    ``tm_moe_compact_layers_last_step``."""

    def loss_fn(params, state, batch):
        tokens, targets = batch
        logits, routing = model.apply({"params": params}, tokens)
        return lm_cross_entropy(logits, targets), routing

    loss_fn.observe_state = lambda state: note_expert_load(
        state["moe_load"], state["moe_rows"])
    return loss_fn
