"""A present-day sparse decoder: RMSNorm, grouped KV heads, full,
sliding-window or selected attention by layer (rotary position or none by
layer), the router read before attention or after the second norm, a gated
feed-forward of routed experts of which this device holds some.

Built from a layer pattern: ``window_layout[l % period]`` says whether layer
``l`` attends within ``window`` (else over the whole causal prefix),
``selected_layout[l % period]`` whether it attends to the ``index_top_k``
keys its own indexer selects for each query, and ``rope_layout[l %
period]`` whether its queries and keys are rotated (else the layer has no
positional encoding at all). Attention is
``parallel.ring_attention.blocked_self_attention`` or
``parallel.selected_attention.selected_self_attention`` (no ``t x t``
tensor a head; on a TPU with heads of a multiple of 128 in fused kernels,
else in loops of XLA operations: the call decides, the model sets nothing);
the experts are ``parallel.ep.moe_local_experts`` (dropless, told which of
all the experts it holds: what the others would add is left out, the part
an exchange across devices would bring). Parameters are float32, the
matrix products run in ``dtype``, the router's product, top-k and softmax
in float32.

One layer, input ``h``: ``r = h W_r`` (before the norm, before attention;
with ``router_after_norm``, ``r = m W_r``); ``a = RMSNorm(h)``; ``h' = h +
Attn(a) W_o`` (with ``qk_norm``, each query and key head through an RMSNorm
of its own before the rotation); ``m = RMSNorm(h')``; ``out = h' + sum_{e in
top_k(r), e held} softmax(r[top_k])_e (act(m W_g^e) * (m W_u^e)) W_d^e``.

A selected layer's indexer reads ``stop_gradient(a)`` in float32 at
precision highest: ``qI = a W_qI`` (``index_heads`` of ``index_dim``), ``kI =
LayerNorm(a W_kI)`` (one head), ``w = a W_w``, ``qI`` and ``kI`` rotated; its
loss ``L_I`` (``selected_self_attention``) is added to the model's, and
reaches the indexer's parameters alone.
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
from ..parallel.selected_attention import (
    SAVED as _ATTN_SAVED,
    note_selected_layers,
    note_selection,
    selected_self_attention,
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
    router_after_norm: bool = False  # the router reads the second norm
    qk_norm: bool = False            # RMSNorm on each query and key head
    index_top_k: Optional[int] = None  # not None: selected attention
    index_heads: int = 16
    index_dim: int = 64
    dtype: Any = jnp.float32

    def _indexer(self, h):
        """The indexer's queries, keys and head weights from the normed
        input, detached from the model: float32, precision highest."""
        b, t, _ = h.shape
        exact = lambda n, name: fnn.Dense(  # noqa: E731
            n, use_bias=False, dtype=jnp.float32,
            precision=lax.Precision.HIGHEST, name=name)
        h = lax.stop_gradient(h).astype(jnp.float32)
        index_q = exact(self.index_heads * self.index_dim, "index_q")(h)
        index_k = fnn.LayerNorm(
            epsilon=self.norm_eps, dtype=jnp.float32, name="index_k_norm"
        )(exact(self.index_dim, "index_k")(h))
        index_w = exact(self.index_heads, "index_w")(h)
        index_q = index_q.reshape(b, t, self.index_heads, self.index_dim)
        if self.rope_theta is not None:
            index_q = rotary(index_q, self.rope_theta)
            index_k = rotary(index_k[:, :, None], self.rope_theta)[:, :, 0]
        return index_q, index_k, index_w

    @fnn.compact
    def __call__(self, x):
        # x: [B, T, D] -> (x, (the tokens each held expert received, the
        # rows the grouped products ran over, the indexer's loss, the
        # pairs it selected: zeros in a layer that selects nothing))
        b, t, d = x.shape
        dense = lambda n, name: fnn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        router = fnn.Dense(
            self.num_experts, use_bias=False, dtype=jnp.float32,
            precision=lax.Precision.HIGHEST, name="router")
        if not self.router_after_norm:
            logits = router(x.astype(jnp.float32))

        h = fnn.RMSNorm(
            epsilon=self.norm_eps, dtype=jnp.float32, name="norm_attn")(x)
        q = dense(self.num_heads * self.head_dim, "q")(h)
        k = dense(self.num_kv_heads * self.head_dim, "k")(h)
        v = dense(self.num_kv_heads * self.head_dim, "v")(h)
        q = q.reshape(b, t, self.num_heads, self.head_dim)
        k = k.reshape(b, t, self.num_kv_heads, self.head_dim)
        v = v.reshape(b, t, self.num_kv_heads, self.head_dim)
        if self.qk_norm:
            head_norm = lambda name: fnn.RMSNorm(  # noqa: E731
                epsilon=self.norm_eps, dtype=jnp.float32, name=name)
            q = head_norm("q_norm")(q).astype(self.dtype)
            k = head_norm("k_norm")(k).astype(self.dtype)
        index_loss = pairs = jnp.float32(0.0)
        selected = self.index_top_k is not None
        if selected:
            with jax.named_scope(_names.SCOPE_ATTN_INDEX):
                index = self._indexer(h)
        with jax.named_scope(
                _names.SCOPE_ATTN_SPARSE if selected
                else _names.SCOPE_ATTN_FULL if self.window is None
                else _names.SCOPE_ATTN_WINDOW):
            if self.rope_theta is not None:
                q, k = rotary(q, self.rope_theta), rotary(k, self.rope_theta)
            if selected:  # the function opens the indexer's scopes itself
                attn, index_loss, pairs = selected_self_attention(
                    q, k, v, *index, top_k=self.index_top_k)
            else:
                attn = blocked_self_attention(
                    q, k, v, window=self.window, block=self.attn_block)
        x = x + dense(d, "o")(attn.reshape(b, t, -1))

        h = fnn.RMSNorm(
            epsilon=self.norm_eps, dtype=jnp.float32, name="norm_moe")(x)
        if self.router_after_norm:
            logits = router(h.astype(jnp.float32))
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
        return x + y.reshape(b, t, d), (load, rows, index_loss, pairs)


class MoEDecoder(fnn.Module):
    """Decoder-only LM over ``MoEDecoderBlock``s. Returns ``(logits [B, T,
    vocab] float32, {"moe_load": [layers, held], "moe_rows": [layers]}
    float32)``: what each layer measured of its routing; a model with
    selected layers adds ``"attn_index_loss"`` and ``"attn_selected_pairs"``
    ``[layers]``: each layer's ``L_I`` and the pairs it selected."""

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
    activation: Callable = jax.nn.relu  # the experts' gate
    router_after_norm: bool = False
    qk_norm: bool = False
    selected_layout: Sequence[int] = (0,)  # 1: the indexer's selection
    index_top_k: int = 2048
    index_heads: int = 16
    index_dim: int = 64
    remat: bool = False  # recompute each block in backward
    dtype: Any = jnp.float32

    def selects(self, i: int) -> bool:
        return bool(self.selected_layout[i % len(self.selected_layout)])

    @property
    def selected_layers(self) -> int:
        return sum(self.selects(i) for i in range(self.num_layers))

    @fnn.compact
    def __call__(self, tokens):
        note_expert_layers(
            tokens.size, self.top_k, self.num_layers, len(self.held))
        note_attention_step()  # each layer's call below counts itself
        note_selected_layers(
            tokens.shape[0], tokens.shape[1], self.selected_layers)
        x = fnn.Embed(
            self.vocab_size, self.d_model, dtype=self.dtype, name="embed"
        )(tokens)
        block_cls = MoEDecoderBlock
        if self.remat:
            # a selected layer's output, log-sum-exps and thresholds are
            # kept: 130 MiB a layer at 16,384 positions buys the selection
            # and the forward attention kernel once a step, not twice
            block_cls = fnn.remat(MoEDecoderBlock, policy=(
                jax.checkpoint_policies.save_only_these_names(_ATTN_SAVED)
                if self.selected_layers else None))
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
                activation=self.activation,
                router_after_norm=self.router_after_norm,
                qk_norm=self.qk_norm,
                index_top_k=self.index_top_k if self.selects(i) else None,
                index_heads=self.index_heads, index_dim=self.index_dim,
                dtype=self.dtype,
                name=f"MoEDecoderBlock_{i}",  # the same with and without remat
            )(x)
            routing.append(measured)
        x = fnn.RMSNorm(
            epsilon=self.norm_eps, dtype=jnp.float32, name="norm")(x)
        logits = fnn.Dense(
            self.vocab_size, use_bias=False, dtype=jnp.float32, name="head"
        )(x)
        load, rows, index_loss, pairs = (
            jnp.stack(a) for a in zip(*routing))
        measured = {"moe_load": load, "moe_rows": rows}
        if self.selected_layers:
            measured.update(
                attn_index_loss=index_loss, attn_selected_pairs=pairs)
        return logits, measured


def init_moe_state(model: MoEDecoder):
    """The model state the engine carries for ``make_moe_lm_loss_fn``: by
    layer, the tokens each held expert received in the last step, and the
    rows the layer's grouped products ran over; with selected layers,
    each layer's indexer loss and the pairs it selected."""
    layers = jnp.zeros((model.num_layers,), jnp.float32)
    state = {
        "moe_load": jnp.zeros(
            (model.num_layers, len(model.held)), jnp.float32),
        "moe_rows": layers,
    }
    if model.selected_layers:
        state.update(attn_index_loss=layers, attn_selected_pairs=layers)
    return state


def make_moe_lm_loss_fn(model: MoEDecoder):
    """Next-token loss for the engine's ``model_state`` path:
    ``loss_fn(params, state, batch) -> (loss, new state)``, the state
    being :func:`init_moe_state`'s (what the step measured of its routing
    rides the path batch norm's statistics take: no further output of the
    step). No auxiliary load-balancing loss; a model with selected layers
    adds each such layer's ``L_I``, whose gradient reaches its indexer
    alone. Where the engine reads an epoch's loss it hands the state to
    ``loss_fn.observe_state``, which sets ``tm_moe_held_routes_last_step``,
    ``tm_moe_max_over_mean_load``, ``tm_moe_grouped_rows_per_step`` and
    ``tm_moe_compact_layers_last_step`` and, with selected layers,
    ``tm_attn_selected_pairs_per_step`` and
    ``tm_attn_index_loss_last_step``."""

    def loss_fn(params, state, batch):
        tokens, targets = batch
        logits, measured = model.apply({"params": params}, tokens)
        loss = lm_cross_entropy(logits, targets)
        if model.selected_layers:
            loss = loss + jnp.sum(measured["attn_index_loss"])
        return loss, measured

    def observe_state(state):
        note_expert_load(state["moe_load"], state["moe_rows"])
        if model.selected_layers:
            note_selection(
                state["attn_index_loss"], state["attn_selected_pairs"],
                [model.selects(i) for i in range(model.num_layers)])

    loss_fn.observe_state = observe_state
    return loss_fn
