"""A present-day sparse decoder: RMSNorm, grouped KV heads, full,
sliding-window or selected attention by layer (rotary position over the
whole head, over a part of it under YaRN's frequencies, or none, by layer)
or, by layer, a gated short convolution where attention stands, a count of
query and KV heads by layer, a sigmoid gate on each head, the router read
before attention or after the second norm, a gated feed-forward of routed
experts of which this device holds some (chosen by the router's rule, which
may add a bias that a rule outside the gradient moves once a step), a
shared expert beside them, leading layers whose feed-forward is dense, and
a vocabulary head of its own or the embedding's table.

Built from a layer pattern: ``window_layout[l % period]`` says whether layer
``l`` attends within ``window`` (else over the whole causal prefix),
``selected_layout[l % period]`` whether it attends to the ``index_top_k``
keys its own indexer selects for each query, and ``rope_layout[l %
period]`` whether its queries and keys are rotated (else the layer has no
positional encoding at all), ``conv_layout[l % period]`` whether its mixer
is the convolution and not attention at all (a pattern may be as long as
the model: a list by layer); ``num_heads`` and ``num_kv_heads`` are one
number or, as the layouts, a pattern by layer. Attention is
``parallel.ring_attention.blocked_self_attention`` or
``parallel.selected_attention.selected_self_attention`` (no ``t x t``
tensor a head; on a TPU with heads of a width the kernels take in fused
kernels, else in loops of XLA operations: the call decides, the model sets
nothing);
the experts are ``parallel.ep.moe_local_experts`` (dropless, told which of
all the experts it holds: what the others would add is left out, the part
an exchange across devices would bring). Parameters are float32, the
matrix products run in ``dtype``, the router's product and its rule
(``route_weights``: top-k and softmax, or sigmoid scores normalised and
scaled) in float32.

One layer, input ``h``: ``r = h W_r`` (before the norm, before attention;
with ``router_after_norm``, ``r = m W_r``); ``a = RMSNorm(h)``; ``h' = h +
Attn(a) W_o`` (with ``qk_norm``, each query and key head through an RMSNorm
of its own before the rotation; with ``head_gate``, head ``n``'s output
times ``sigmoid(a W_g)_n``, one number a token and head, before ``W_o``);
``m = RMSNorm(h')``; ``out = h' + sum_{e in chosen(r), e held} w_e (act(m
W_g^e) * (m W_u^e)) W_d^e`` with ``chosen`` and ``w`` the router's rule's
(by default the ``top_k`` largest ``r`` and the softmax over them), plus,
with ``shared_width``, ``(act(m S_g) * (m S_u)) S_d``, an expert every token
takes at weight 1. The first ``dense_layers`` layers have no router and no
experts: ``out = h' + (act(m D_g) * (m D_u)) D_d``.

A convolution layer (``conv_taps``) has no query, key or value: ``[B | C |
x] = a W_in``, three column blocks of ``d`` in that order; ``h' = h + (C *
conv(B * x)) W_out``, the convolution causal and depthwise over ``conv_taps``
positions, no bias, no activation (``parallel.ssm.gated_short_conv``). What
follows the mixer is the same.

With ``expert_bias`` a layer's router takes a bias ``[num_experts]`` beside
its input, ``route_weights`` is called with it and gives the rule
(``ep.biased_sigmoid_route_weights`` with its numbers bound), and the layer
measures two things more: the tokens that chose each of ALL the experts, and
the routes the bias turned. The bias is no parameter: the model is called
with ``moe_bias``, a ``[num_experts]`` an expert layer, the engine carries
it as model state, and ``make_moe_lm_loss_fn`` moves it once a step, outside the
gradient: ``b_e <- b_e + u sign(mean(c) - c_e)`` from that step's counts
``c`` (summed over ``axis_name``'s devices where the model names one, so
that every device ends the step with the same bias).

**A layer held by share.** The heads a layer is built with are the heads
this device holds (a KV head with its group of query heads),
``dense_width`` the columns of the dense feed-forward it holds, ``held``
its experts. Each is a slice of ``W_q``, ``W_k``, ``W_v``, ``W_g``
by column and of ``W_o`` (or ``D_d``) by row, so what the layer adds to the
residual stream is this device's part of a sum; the parts held elsewhere
are left out, as the absent experts' are, and the partial result goes on.
The sum over the devices (a ``psum`` of the ``o`` and ``down`` partials) is
not made here.

A selected layer's indexer reads ``stop_gradient(a)`` in float32 at
precision highest: ``qI = a W_qI`` (``index_heads`` of ``index_dim``), ``kI =
LayerNorm(a W_kI)`` (one head), ``w = a W_w``, ``qI`` and ``kI`` rotated; its
loss ``L_I`` (``selected_self_attention``) is added to the model's, and
reaches the indexer's parameters alone.
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple, Optional, Sequence, Union

import flax.linen as fnn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import telemetry as _telemetry
from ..parallel.ep import (
    note_expert_bias,
    note_expert_layers,
    note_expert_load,
    softmax_route_weights,
)
from ..parallel.ssm import gated_short_conv, note_gated_conv_step
from ..parallel.ring_attention import (
    blocked_self_attention,
    note_attention_step,
)
from ..parallel.selected_attention import (
    note_selected_layers,
    note_selection,
    selected_self_attention,
)
from ..telemetry import names as _names
from .embedding import TokenEmbed
from .lm import (
    HEAD_GATE,
    INDEX,
    MIXER_IN,
    MLP_GATE,
    MLP_UP,
    QKV,
    RESIDUAL,
    ROUTER,
    product,
    products_kept,
    recomputed,
    rotary,
    sparse_feed_forward,
    taps_init,
)
from .lm_head import VocabHead


class Rotary(NamedTuple):
    """A layer kind's rotation where it is not the whole head at one
    ``theta``: the first ``width`` of each head rotated (its halves against
    each other), the rest passed on as it is; the frequencies YaRN's
    (arXiv:2309.00071), stretched by ``factor`` from ``original_positions``
    where a frequency turns fewer than ``beta_slow`` times over them, left
    alone where it turns more than ``beta_fast`` times, blended between;
    cos and sin times ``attention_factor``."""

    theta: float
    width: int
    factor: float
    original_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


def yarn_inv_freq(rope: Rotary) -> np.ndarray:
    """The ``width / 2`` frequencies of ``rope``, float32, from its numbers
    alone: ``f_i = theta^(-2i / width)``; ``r_i = clip((i - low) / (high -
    low), 0, 1)`` with ``low`` (``high``) the index whose frequency turns
    ``beta_fast`` (``beta_slow``) times over the original positions, rounded
    down (up) and kept in ``[0, width - 1]``; ``r_i f_i / factor + (1 - r_i)
    f_i``."""
    dim = rope.width
    freq = rope.theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns_at(turns):
        return dim * math.log(rope.original_positions / (
            turns * 2 * math.pi)) / (2 * math.log(rope.theta))

    low = min(max(math.floor(turns_at(rope.beta_fast)), 0), dim - 1)
    high = min(max(math.ceil(turns_at(rope.beta_slow)), 0), dim - 1)
    ramp = np.clip(
        (np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (ramp * freq / rope.factor + (1 - ramp) * freq).astype(np.float32)


def rotary_part(x, rope: Rotary):
    """``rope``'s rotation of ``x`` ``[b, t, h, d]``, positions ``0 .. t -
    1``; float32 inside, ``x``'s dtype out."""
    inv = jnp.asarray(yarn_inv_freq(rope))
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = (rope.attention_factor * jnp.cos(angle))[:, None, :]
    sin = (rope.attention_factor * jnp.sin(angle))[:, None, :]
    turned, passed = jnp.split(x.astype(jnp.float32), [rope.width], axis=-1)
    x1, x2 = jnp.split(turned, 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, passed],
        axis=-1).astype(x.dtype)


def _of_layer(value, i: int):
    """``value`` of layer ``i``: one number for every layer, or a pattern
    by layer as the layouts are."""
    return value if isinstance(value, int) else value[i % len(value)]


class MoEDecoderBlock(fnn.Module):
    num_heads: int              # query and KV heads this layer holds here
    num_kv_heads: int
    head_dim: int
    expert_width: int
    num_experts: int            # the router's outputs: ALL the experts
    top_k: int
    held: Sequence[int]         # ids of the experts this device holds
    window: Optional[int] = None       # None: the whole causal prefix
    rope_theta: Optional[float] = None  # None: no positional encoding
    rope: Optional[Rotary] = None      # not None: in place of rope_theta
    norm_eps: float = 1e-6
    attn_block: int = 1024
    activation: Callable = jax.nn.relu
    router_after_norm: bool = False  # the router reads the second norm
    qk_norm: bool = False            # RMSNorm on each query and key head
    index_top_k: Optional[int] = None  # not None: selected attention
    index_heads: int = 16
    index_dim: int = 64
    head_gate: bool = False          # a sigmoid gate on each head's output
    route_weights: Callable = softmax_route_weights  # the router's rule
    shared_width: Optional[int] = None  # not None: a shared expert
    dense_width: Optional[int] = None   # not None: a dense feed-forward of
    #                                     these columns, no router or expert
    conv_taps: Optional[int] = None  # not None: the mixer is a gated short
    #                                  convolution of these taps, no attention
    expert_bias: bool = False  # the router's rule takes the layer's bias:
    #                            ``route_weights(bias)`` gives the rule
    dtype: Any = jnp.float32

    def _indexer(self, h):
        """The indexer's queries, keys and head weights from the normed
        input, detached from the model: float32, precision highest."""
        b, t, d = h.shape
        exact = lambda n, name: product(fnn.Dense(  # noqa: E731
            n, use_bias=False, dtype=jnp.float32,
            precision=lax.Precision.HIGHEST, name=name)(h), INDEX, d,
            passes=6)
        h = lax.stop_gradient(h).astype(jnp.float32)
        index_q = exact(self.index_heads * self.index_dim, "index_q")
        index_k = fnn.LayerNorm(
            epsilon=self.norm_eps, dtype=jnp.float32, name="index_k_norm"
        )(exact(self.index_dim, "index_k"))
        index_w = exact(self.index_heads, "index_w")
        index_q = index_q.reshape(b, t, self.index_heads, self.index_dim)
        if self.rope_theta is not None:
            index_q = rotary(index_q, self.rope_theta)
            index_k = rotary(index_k[:, :, None], self.rope_theta)[:, :, 0]
        return index_q, index_k, index_w

    def _dense(self, n, name):
        return fnn.Dense(n, use_bias=False, dtype=self.dtype, name=name)

    def _norm(self, name):
        return fnn.RMSNorm(
            epsilon=self.norm_eps, dtype=jnp.float32, name=name)

    def _attention(self, x, h):
        """``(x + Attn(h) W_o, the indexer's loss, the pairs it selected)``
        from the stream ``x`` and its normed copy ``h``; zeros in a layer
        that selects nothing."""
        b, t, d = x.shape
        dense, norm = self._dense, self._norm
        with jax.named_scope(_names.SCOPE_ATTN_PROJ):
            # named flat, before the reshape to heads
            q = product(dense(self.num_heads * self.head_dim, "q")(h), QKV, d)
            k = product(
                dense(self.num_kv_heads * self.head_dim, "k")(h), QKV, d)
            v = product(
                dense(self.num_kv_heads * self.head_dim, "v")(h), QKV, d)
        # the reshapes on either side of the attention stand under no
        # scope: XLA merges one with the attention's own reshape next to it
        # into a single copy that bears both op_names, and a name here
        # would take that copy out of the attention's scope, which the
        # benchmark's older metrics read
        q = q.reshape(b, t, self.num_heads, self.head_dim)
        k = k.reshape(b, t, self.num_kv_heads, self.head_dim)
        v = v.reshape(b, t, self.num_kv_heads, self.head_dim)
        if self.qk_norm:
            with jax.named_scope(_names.SCOPE_LM_NORM):
                q = norm("q_norm")(q).astype(self.dtype)
                k = norm("k_norm")(k).astype(self.dtype)
        if self.head_gate:
            with jax.named_scope(_names.SCOPE_ATTN_GATE):
                gate = jax.nn.sigmoid(product(
                    dense(self.num_heads, "head_gate")(h), HEAD_GATE, d
                ).astype(jnp.float32))
        index_loss = pairs = jnp.float32(0.0)
        selected = self.index_top_k is not None
        if selected:
            with jax.named_scope(_names.SCOPE_ATTN_INDEX):
                index = self._indexer(h)
        with jax.named_scope(
                _names.SCOPE_ATTN_SPARSE if selected
                else _names.SCOPE_ATTN_FULL if self.window is None
                else _names.SCOPE_ATTN_WINDOW):
            if self.rope is not None:
                q, k = rotary_part(q, self.rope), rotary_part(k, self.rope)
            elif self.rope_theta is not None:
                q, k = rotary(q, self.rope_theta), rotary(k, self.rope_theta)
            if selected:  # the function opens the indexer's scopes itself
                attn, index_loss, pairs = selected_self_attention(
                    q, k, v, *index, top_k=self.index_top_k)
            else:
                attn = blocked_self_attention(
                    q, k, v, window=self.window, block=self.attn_block)
        if self.head_gate:
            with jax.named_scope(_names.SCOPE_ATTN_GATE):
                attn = (attn * gate[..., None]).astype(attn.dtype)
        attn = attn.reshape(b, t, -1)
        with jax.named_scope(_names.SCOPE_ATTN_PROJ):
            x = product(x + dense(d, "o")(attn), RESIDUAL, attn.shape[-1])
        return x, index_loss, pairs

    def _short_conv(self, x, h):
        """``x + (C * conv(B * x')) W_out`` with ``[B | C | x'] = h W_in``,
        from the stream ``x`` and its normed copy ``h``."""
        d = x.shape[-1]
        with jax.named_scope(_names.SCOPE_SCONV_PROJ):
            bcx = product(self._dense(3 * d, "in_proj")(h), MIXER_IN, d)
        with jax.named_scope(_names.SCOPE_SCONV):
            mixed = gated_short_conv(bcx, self.param(
                "conv_kernel", taps_init, (self.conv_taps, d), jnp.float32))
        with jax.named_scope(_names.SCOPE_SCONV_PROJ):
            return product(
                x + self._dense(d, "out_proj")(mixed), RESIDUAL, d)

    @fnn.compact
    def __call__(self, x, bias=None):
        # x: [B, T, D] -> (x, (the tokens each held expert received, the
        # rows the grouped products ran over, the indexer's loss, the
        # pairs it selected: zeros in a layer that selects nothing; with
        # ``expert_bias``, the tokens that chose each of ALL the experts
        # under the router's ``bias`` [num_experts] (None: zeros) and the
        # routes the bias turned))
        d = x.shape[-1]
        dense, norm = self._dense, self._norm

        def gated(m, width, name):
            """``(act(m W_gate) * (m W_up)) W_down``, ``width`` columns."""
            return dense(d, name + "_down")(
                self.activation(product(
                    dense(width, name + "_gate")(m), MLP_GATE, d))
                * product(dense(width, name + "_up")(m), MLP_UP, d))

        sparse = self.dense_width is None
        if sparse and not self.router_after_norm:
            with jax.named_scope(_names.SCOPE_MOE_ROUTER):
                logits = product(fnn.Dense(
                    self.num_experts, use_bias=False, dtype=jnp.float32,
                    precision=lax.Precision.HIGHEST, name="router"
                )(x.astype(jnp.float32)), ROUTER, d, passes=6)

        with jax.named_scope(_names.SCOPE_LM_NORM):
            h = norm("norm_attn")(x)
        index_loss = pairs = jnp.float32(0.0)
        if self.conv_taps is None:
            x, index_loss, pairs = self._attention(x, h)
        else:
            x = self._short_conv(x, h)

        with jax.named_scope(_names.SCOPE_LM_NORM):
            h = norm("norm_moe")(x)
        n, f = len(self.held), self.expert_width
        zeros = (jnp.zeros((self.num_experts,), jnp.float32),
                 jnp.float32(0.0)) if self.expert_bias else ()
        if not sparse:
            with jax.named_scope(_names.SCOPE_MOE_DENSE):
                x = x + gated(h.astype(self.dtype), self.dense_width, "mlp")
            return x, (jnp.zeros((n,), jnp.float32), jnp.float32(0.0),
                       index_loss, pairs, *zeros)
        if self.expert_bias and bias is None:
            bias = zeros[0]
        x, load, rows, *noted = sparse_feed_forward(
            self, x, h, expert_width=f, num_experts=self.num_experts,
            top_k=self.top_k, held=self.held, activation=self.activation,
            dtype=self.dtype,
            logits=None if self.router_after_norm else logits,
            route_weights=self.route_weights,
            shared_width=self.shared_width,
            route_bias=bias if self.expert_bias else None)
        return x, (load, rows, index_loss, pairs, *(noted[0] if noted else ()))


class MoEDecoder(fnn.Module):
    """Decoder-only LM over ``MoEDecoderBlock``s. Returns ``(logits [B, T,
    vocab] float32, {"moe_load": [expert layers, held], "moe_rows": [expert
    layers]} float32)``: what each layer with experts measured of its
    routing; a model with selected layers adds ``"attn_index_loss"`` and
    ``"attn_selected_pairs"`` ``[layers]``: each layer's ``L_I`` and the
    pairs it selected; a model with ``expert_bias`` adds ``"moe_counts"``
    ``[expert layers, num_experts]``, the tokens that chose each of ALL the
    experts under ``moe_bias`` (a ``[num_experts]`` float32 an expert
    layer; None: zeros), and ``"moe_biased_routes"`` ``[expert layers]``, the
    routes the bias turned. With ``targets``, the mean next-token loss
    stands where the logits do (``lm_head.VocabHead``).

    What differs by layer is given as a pattern, repeated over the depth:
    ``window_layout``, ``rope_layout``, ``selected_layout``,
    ``conv_layout`` (1: the layer's mixer is a gated short convolution of
    ``conv_taps`` taps, not attention), and ``num_heads`` / ``num_kv_heads``
    where they are sequences (one number: every layer's). ``rope_full`` is the rotation of the layers that attend
    over the whole prefix where it is not the window layers' (``rope_theta``
    over the whole head). The first ``dense_layers`` layers have a dense
    feed-forward of ``dense_width`` columns in place of the experts. With
    ``tied_head`` the vocabulary head is the embedding's table. The heads,
    the columns, the experts and the vocabulary given are the ones this
    device holds (the module's docstring: a layer held by share).
    ``axis_name``: the axis of the devices that bring their own sequences
    to the same experts, over which a step's counts are summed before they
    move the bias (``make_moe_lm_loss_fn``); the forward pass makes no
    collective."""

    vocab_size: int = 256
    num_layers: int = 4
    d_model: int = 128
    num_heads: Union[int, Sequence[int]] = 4
    num_kv_heads: Union[int, Sequence[int]] = 2
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
    head_gate: bool = False
    rope_full: Optional[Rotary] = None
    route_weights: Callable = softmax_route_weights
    shared_width: Optional[int] = None
    dense_layers: int = 0
    dense_width: int = 0
    conv_layout: Sequence[int] = (0,)  # 1: a gated short convolution
    conv_taps: int = 3
    expert_bias: bool = False  # ``route_weights(bias)`` gives the rule
    bias_update_rate: float = 1e-3  # ``u`` of the bias's rule
    tied_head: bool = False
    axis_name: Optional[str] = None
    remat: bool = False  # recompute each block in backward, but for what
    #                      its attention's forward kernels kept and the
    #                      products' results the step has room for:
    #                      ``recomputed``, ``products_kept``
    dtype: Any = jnp.float32

    def selects(self, i: int) -> bool:
        return bool(self.selected_layout[i % len(self.selected_layout)])

    def convolves(self, i: int) -> bool:
        return bool(self.conv_layout[i % len(self.conv_layout)])

    @property
    def selected_layers(self) -> int:
        return sum(self.selects(i) for i in range(self.num_layers))

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.dense_layers

    @fnn.compact
    def __call__(self, tokens, targets=None, moe_bias=None):
        if not 0 <= self.dense_layers < self.num_layers:
            raise ValueError(
                f"dense_layers must leave a layer with experts, got "
                f"{self.dense_layers} of {self.num_layers}")
        blocks = []
        for i in range(self.num_layers):
            windowed = self.window_layout[i % len(self.window_layout)]
            rotated = self.rope_layout[i % len(self.rope_layout)]
            blocks.append(dict(
                num_heads=_of_layer(self.num_heads, i),
                num_kv_heads=_of_layer(self.num_kv_heads, i),
                head_dim=self.head_dim, expert_width=self.expert_width,
                num_experts=self.num_experts, top_k=self.top_k,
                held=tuple(self.held),
                window=self.window if windowed else None,
                rope_theta=self.rope_theta if rotated else None,
                rope=self.rope_full if rotated and not windowed else None,
                norm_eps=self.norm_eps, attn_block=self.attn_block,
                activation=self.activation,
                router_after_norm=self.router_after_norm,
                qk_norm=self.qk_norm,
                index_top_k=self.index_top_k if self.selects(i) else None,
                index_heads=self.index_heads, index_dim=self.index_dim,
                head_gate=self.head_gate, route_weights=self.route_weights,
                shared_width=self.shared_width,
                dense_width=(
                    self.dense_width if i < self.dense_layers else None),
                conv_taps=self.conv_taps if self.convolves(i) else None,
                expert_bias=self.expert_bias, dtype=self.dtype,
                name=f"MoEDecoderBlock_{i}"))  # with and without remat
        block_cls = MoEDecoderBlock
        if self.remat:
            # every layer kind keeps what its attention's forward kernels
            # made. A selected layer: the output, log-sum-exps, thresholds
            # and its panels of float32 index scores, 130 + 640 MiB a layer
            # at 16,384 positions, which buy the index scores, the selection
            # and the forward attention kernels once a step, not twice. A
            # full or windowed layer: the fused kernels' output and
            # log-sum-exp (2 B x head_dim + 4 B a query and head). And the
            # dense products' results the step has room for
            # (``products_kept``: it traces the blocks, so it stands before
            # the attention calls are counted)
            block_cls = recomputed(MoEDecoderBlock, keep=products_kept(
                self, MoEDecoderBlock, blocks, jax.ShapeDtypeStruct(
                    tokens.shape + (self.d_model,), self.dtype),
                self.vocab_size))
        note_expert_layers(
            tokens.size, self.top_k, self.expert_layers, len(self.held))
        _telemetry.metrics.gauge(
            _names.GAUGE_ATTN_HEADS_HELD,
            "query heads this rank holds, summed over the layers of the "
            "step most recently traced").set(sum(
                _of_layer(self.num_heads, i) for i in range(self.num_layers)
                if not self.convolves(i)))
        note_attention_step()  # each layer's call below counts itself
        note_selected_layers(
            tokens.shape[0], tokens.shape[1], self.selected_layers)
        convolved = sum(self.convolves(i) for i in range(self.num_layers))
        if convolved:
            note_gated_conv_step(convolved, tokens.shape + (self.d_model,))
        embed = TokenEmbed(
            self.vocab_size, self.d_model, dtype=self.dtype, name="embed")
        with jax.named_scope(_names.SCOPE_LM_EMBED):
            x = embed(tokens)
        routing = []
        for i, block in enumerate(blocks):
            # an expert layer's bias, where the model was handed any
            bias = (moe_bias[i - self.dense_layers],) if (
                moe_bias is not None and i >= self.dense_layers) else ()
            x, measured = block_cls(**block)(x, *bias)
            routing.append(measured)
        with jax.named_scope(_names.SCOPE_LM_NORM):
            x = fnn.RMSNorm(
                epsilon=self.norm_eps, dtype=jnp.float32, name="norm")(x)
        # the logits, or with ``targets`` the mean next-token loss
        logits = VocabHead(
            self.vocab_size, use_bias=False, dtype=jnp.float32,
            name="head")(x, targets, *(
                (embed.embedding,) if self.tied_head else ()))
        load, rows, index_loss, pairs, *noted = (
            jnp.stack(a) for a in zip(*routing))
        # a dense layer routes nothing: its zeros are no expert layer's
        measured = {"moe_load": load[self.dense_layers:],
                    "moe_rows": rows[self.dense_layers:]}
        if self.selected_layers:
            measured.update(
                attn_index_loss=index_loss, attn_selected_pairs=pairs)
        if self.expert_bias:
            counts, turned = (a[self.dense_layers:] for a in noted)
            measured.update(moe_counts=counts, moe_biased_routes=turned)
        return logits, measured


def init_moe_state(model: MoEDecoder):
    """The model state the engine carries for ``make_moe_lm_loss_fn``: by
    layer with experts, the tokens each held expert received in the last
    step, and the rows the layer's grouped products ran over; with selected
    layers, each layer's indexer loss and the pairs it selected; with
    ``expert_bias``, each router's bias over ALL its experts, from 0 (the
    one entry the next step's forward pass READS; a leaf a layer, so that
    whoever compares a state leaf by leaf beside the loads' thousands, as
    the benchmark does, reads each router's), and the routes the bias
    turned in the last step."""
    layers = jnp.zeros((model.num_layers,), jnp.float32)
    state = {
        "moe_load": jnp.zeros(
            (model.expert_layers, len(model.held)), jnp.float32),
        "moe_rows": jnp.zeros((model.expert_layers,), jnp.float32),
    }
    if model.selected_layers:
        state.update(attn_index_loss=layers, attn_selected_pairs=layers)
    if model.expert_bias:
        state.update(
            moe_bias=[jnp.zeros((model.num_experts,), jnp.float32)
                      for _ in range(model.expert_layers)],
            moe_biased_routes=jnp.zeros((model.expert_layers,), jnp.float32))
    return state


def make_moe_lm_loss_fn(model: MoEDecoder):
    """Next-token loss for the engine's ``model_state`` path:
    ``loss_fn(params, state, batch) -> (loss, new state)``, the state
    being :func:`init_moe_state`'s (what the step measured of its routing
    rides the path batch norm's statistics take: no further output of the
    step). No auxiliary load-balancing loss; a model with selected layers
    adds each such layer's ``L_I``, whose gradient reaches its indexer
    alone.

    With ``expert_bias`` the forward pass reads ``state["moe_bias"]`` and
    the new state holds the bias the step's counts moved it to, outside the
    gradient: ``b_e + u sign(mean(c) - c_e)``, ``c_e`` the tokens of the
    step that chose expert ``e`` among ALL the layer's experts, ``u`` the
    model's ``bias_update_rate`` (DeepSeek-V3's auxiliary-loss-free
    balancing, arXiv:2408.15664). Where the model names an ``axis_name``
    the counts are ``psum``med over it first: every device then ends the
    step with the same bias, and the engine's ``pmean`` of the model state
    is the identity on it.

    Where the engine reads an epoch's loss it hands the state to
    ``loss_fn.observe_state``, which sets ``tm_moe_held_routes_last_step``,
    ``tm_moe_max_over_mean_load``, ``tm_moe_grouped_rows_per_step`` and
    ``tm_moe_compact_layers_last_step``; with selected layers,
    ``tm_attn_selected_pairs_per_step`` and
    ``tm_attn_index_loss_last_step``; with ``expert_bias``,
    ``tm_moe_bias_max_abs`` and ``tm_moe_biased_routes_last_step``."""

    def loss_fn(params, state, batch):
        tokens, targets = batch
        bias = (state["moe_bias"],) if model.expert_bias else ()
        loss, measured = model.apply(
            {"params": params}, tokens, targets, *bias)
        if model.selected_layers:
            loss = loss + jnp.sum(measured["attn_index_loss"])
        if model.expert_bias:
            counts = measured.pop("moe_counts")
            if model.axis_name is not None:
                counts = lax.psum(counts, model.axis_name)
            moved = model.bias_update_rate * jnp.sign(
                jnp.mean(counts, axis=-1, keepdims=True) - counts)
            measured["moe_bias"] = [b + m for b, m in zip(bias[0], moved)]
        return loss, measured

    def observe_state(state):
        note_expert_load(state["moe_load"], state["moe_rows"])
        if model.selected_layers:
            note_selection(
                state["attn_index_loss"], state["attn_selected_pairs"],
                [model.selects(i) for i in range(model.num_layers)])
        if model.expert_bias:
            note_expert_bias(state["moe_bias"], state["moe_biased_routes"])

    loss_fn.observe_state = observe_state
    return loss_fn
