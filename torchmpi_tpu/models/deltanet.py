"""A gated-delta decoder: a layer's mixer is EITHER a Gated DeltaNet mixer
(the gated delta rule of ``parallel/deltanet.py`` behind a short causal
convolution; arXiv:2412.06464) OR softmax attention with an elementwise
output gate, by the layer's place in a period, and under both sits an expert
layer with a sigmoid-gated shared expert; every norm but the delta mixer's
output norm is zero-centred (the Qwen3-Next family's).

A sibling of ``models/decoder.py``'s, ``models/hybrid.py``'s and
``models/retentive.py``'s blocks, not more fields on any: the two mixers
share no parameter shape, and those files' lowered steps are pinned letter
for letter. What they share is called, not copied: attention is
``parallel.ring_attention.blocked_self_attention``, the rotation
``lm.rotary`` (over the first ``rotary_dim`` of a head), the convolution
with its SiLU ``parallel.ssm.causal_conv1d_silu``, the sparse feed-forward half
``lm.sparse_feed_forward`` (``parallel.ep.moe_local_experts`` under it), the
recomputation ``lm.recomputed``, the loss ``lm_head.VocabHead``'s, the
scopes ``telemetry.names``'; the sequence operation is
``parallel.deltanet.gated_delta_rule``.

``zrms(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``, ``w`` from 0: weight decay
pulls the scale to 1, not to 0. Every layer, input ``x`` ``[t, D]``: ``h = x
+ Mixer(zrms(x))``; ``out = h + MoE(zrms(h))``.

Linear mixer, ``u`` its normed input, ``K`` key heads ``j`` of ``dk``, ``V``
value heads ``n`` of ``dv``, ``n`` reading key head ``n // (V // K)``: ``[q |
k | v | z] = u W_qkvz`` (``K dk + K dk + V dv + V dv`` columns); ``[b | a] =
u W_ba`` (``V + V``); ``[q | k | v] <- silu(conv([q | k | v]))``, causal,
depthwise, zeros before position 0, no bias; ``q_j <- q_j / sqrt(sum q_j^2 +
1e-6) / sqrt(dk)``, ``k_j <- k_j / sqrt(sum k_j^2 + 1e-6)``; ``beta_n =
sigmoid(b_n)``; ``g_n = -exp(A_log_n) softplus(a_n + dt_bias_n)``, float32;
``o`` the gated delta rule's; ``y_n = o_n / sqrt(mean(o_n^2) + eps) * w_o *
silu(z_n)`` (``w_o`` ``[dv]`` from 1: NOT zero-centred); ``Mixer = [y_0 ..
y_{V-1}] W_out``.

Full mixer, ``H`` query heads of ``d`` reading ``H_kv`` KV heads: ``[q_n |
gate_n] = (u W_q)_n`` (``2 d`` a head); ``k = u W_k``, ``v = u W_v``; ``q_n
<- rot(zrms_d(q_n))``, ``k_m <- rot(zrms_d(k_m))``, the first ``rotary_dim``
of a head rotated, its halves against each other, the rest passed on;
causal softmax at ``1 / sqrt(d)``; ``Mixer = [att_n * sigmoid(gate_n)]_n
W_o``, the gate elementwise.

MoE, ``m`` its normed input: the router's softmax over all its outputs in
float32, the ``top_k`` largest, each over the chosen ones' sum
(``ep.softmax_route_weights``: the same numbers); the held experts' part of
``sum w_e (silu(m G_e) * (m U_e)) D_e`` plus ``sigmoid(m . w_s) (silu(m G_s)
* (m U_s)) D_s``, the shared expert whole.

**Held by share** (``models/decoder.py``'s sense): ``held`` says which of
the router's experts are here; what the others would add is left out and the
partial result goes on. Both mixers, the router, the shared expert and the
norms are whole. The vocabulary given is the slice held.

Parameters are float32; the products run in ``dtype``; the norms, the
convolution, the gates, the decays and the carried state in float32.
"""

from __future__ import annotations

import math
from typing import Any, Sequence

import flax.linen as fnn
import jax
import jax.numpy as jnp

from ..parallel.deltanet import gated_delta_rule, note_gdn_step
from ..parallel.ep import note_expert_layers
from ..parallel.ring_attention import (
    blocked_self_attention,
    note_attention_step,
)
from ..parallel.ssm import causal_conv1d_silu, note_conv_step
from ..telemetry import names as _names
from .embedding import TokenEmbed
from .lm import (
    MIXER_GATES,
    MIXER_IN,
    QKV,
    RESIDUAL,
    a_log_init,
    dt_bias_init,
    product,
    products_kept,
    recomputed,
    rotary,
    sparse_feed_forward,
    taps_init,
)
from .lm_head import VocabHead

L2_EPS = 1e-6  # beside the sum of squares of a query or key head


class ZeroCentredRMSNorm(fnn.Module):
    """``x / sqrt(mean(x^2) + eps) * (1 + scale)`` over the last axis,
    ``scale`` from 0; float32."""

    epsilon: float = 1e-6

    @fnn.compact
    def __call__(self, x):
        x = x.astype(jnp.float32)
        scale = self.param(
            "scale", fnn.initializers.zeros, (x.shape[-1],), jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.epsilon
        ) * (1.0 + scale)


class GatedDeltaDecoderBlock(fnn.Module):
    linear: bool         # the mixer: the gated delta rule, else attention
    num_heads: int       # the full mixer's query heads
    num_kv_heads: int
    head_dim: int
    rotary_dim: int      # the first of a head that are rotated
    key_heads: int       # the linear mixer's
    value_heads: int
    key_dim: int
    value_dim: int
    expert_width: int
    shared_width: int
    num_experts: int     # the router's outputs: ALL the experts
    top_k: int
    held: Sequence[int]  # ids of the experts this device holds
    conv_width: int = 4
    chunk: int = 64      # the program's, not the model's
    rope_theta: float = 1e7
    norm_eps: float = 1e-6
    attn_block: int = 1024
    dtype: Any = jnp.float32

    def _dense(self, n, name):
        return fnn.Dense(n, use_bias=False, dtype=self.dtype, name=name)

    def _norm(self, name):
        return ZeroCentredRMSNorm(epsilon=self.norm_eps, name=name)

    def _linear_mixer(self, u):
        """The delta mixer's part from the normed input ``u`` ``[b, t, d]``
        float32."""
        b, t, d = u.shape
        f32 = jnp.float32
        kw = self.key_heads * self.key_dim
        vw = self.value_heads * self.value_dim
        with jax.named_scope(_names.SCOPE_GDN_PROJ):
            h = u.astype(self.dtype)
            qkv, z = jnp.split(product(
                self._dense(2 * kw + 2 * vw, "in_qkvz")(h), MIXER_IN, d),
                [2 * kw + vw], axis=-1)
            # small, so that ``g`` starts at ``-A dt`` and ``beta`` near 1/2
            bb, a = jnp.split(product(fnn.Dense(
                2 * self.value_heads, use_bias=False, dtype=self.dtype,
                kernel_init=fnn.initializers.normal(0.02 / math.sqrt(d)),
                name="in_ba")(h), MIXER_GATES, d).astype(f32), 2, axis=-1)

        def convolved(qkv, taps):
            """``q`` and ``k`` normalised and rounded as the rule's products
            take them, ``v`` float32, from the product's ``[q | k | v]``."""
            with jax.named_scope(_names.SCOPE_GDN_CONV):
                qkv = causal_conv1d_silu(
                    qkv, taps, jnp.zeros(taps.shape[1:], f32))
            q, k, v = jnp.split(qkv, [kw, 2 * kw], axis=-1)
            q = q.reshape(b, t, self.key_heads, self.key_dim)
            k = k.reshape(b, t, self.key_heads, self.key_dim)
            with jax.named_scope(_names.SCOPE_GDN_GATE):
                unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
                    jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)
                return ((unit(q) / math.sqrt(self.key_dim)).astype(self.dtype),
                        unit(k).astype(self.dtype),
                        v.reshape(b, t, self.value_heads, self.value_dim))

        # made again in backward from the bfloat16 product: kept, the
        # convolution's float32 result and the normalised heads are arrays
        # of [t, 8192] (1 GiB at 16,384) that outlive the rule's backward.
        # The convolution keeps nothing in float32 itself (its own
        # derivative rule: ``causal_conv1d_silu``); what this spares is the
        # L2 norms' operands, at one more pass of the forward kernel
        q, k, v = jax.checkpoint(convolved)(qkv, self.param(
            "conv_kernel", taps_init, (self.conv_width, 2 * kw + vw), f32))
        with jax.named_scope(_names.SCOPE_GDN_GATE):
            beta = jax.nn.sigmoid(bb)
            g = -jnp.exp(self.param(
                "A_log", a_log_init, (self.value_heads,), f32)
            ) * jax.nn.softplus(a + self.param(
                "dt_bias", dt_bias_init, (self.value_heads,), f32))
        # opens ``tm.lm.gdn_gate``, ``gdn_chunk`` and ``gdn_state`` itself
        o = gated_delta_rule(q, k, v, g, beta, chunk=self.chunk,
                             dtype=self.dtype)
        with jax.named_scope(_names.SCOPE_GDN_GATE):
            y = o * jax.lax.rsqrt(
                jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                + self.norm_eps
            ) * self.param(
                "out_norm", fnn.initializers.ones, (self.value_dim,), f32
            ) * jax.nn.silu(z.astype(f32)).reshape(o.shape)
        with jax.named_scope(_names.SCOPE_GDN_PROJ):
            return self._dense(d, "out")(
                y.astype(self.dtype).reshape(b, t, vw))

    def _full_mixer(self, u):
        """The gated softmax attention's part from the normed input ``u``
        ``[b, t, d]`` float32."""
        b, t, d = u.shape
        n, kv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        with jax.named_scope(_names.SCOPE_ATTN_PROJ):
            h = u.astype(self.dtype)
            # named flat, before the reshape to heads
            q_gate = product(self._dense(2 * n * hd, "q")(h), QKV, d)
            k = product(self._dense(kv * hd, "k")(h), QKV, d)
            v = product(self._dense(kv * hd, "v")(h), QKV, d)
        # the reshapes stand under no scope, as in models/decoder.py
        q, gate = jnp.split(q_gate.reshape(b, t, n, 2 * hd), 2, axis=-1)
        k = k.reshape(b, t, kv, hd)
        v = v.reshape(b, t, kv, hd)
        with jax.named_scope(_names.SCOPE_LM_NORM):
            q = self._norm("q_norm")(q).astype(self.dtype)
            k = self._norm("k_norm")(k).astype(self.dtype)
        with jax.named_scope(_names.SCOPE_ATTN_FULL):
            turn = lambda x: jnp.concatenate(  # noqa: E731
                [rotary(x[..., :self.rotary_dim], self.rope_theta),
                 x[..., self.rotary_dim:]], axis=-1)
            attn = blocked_self_attention(
                turn(q), turn(k), v, block=self.attn_block)
        with jax.named_scope(_names.SCOPE_ATTN_GATE):
            attn = (attn * jax.nn.sigmoid(gate.astype(jnp.float32))
                    ).astype(attn.dtype)
        attn = attn.reshape(b, t, n * hd)
        with jax.named_scope(_names.SCOPE_ATTN_PROJ):
            return self._dense(d, "o")(attn)

    @fnn.compact
    def __call__(self, x):
        # x: [B, T, D] -> (x, (the tokens each held expert received, the
        # rows the grouped products ran over))
        with jax.named_scope(_names.SCOPE_LM_NORM):
            u = self._norm("norm_mix")(x)
        mix = self._linear_mixer(u) if self.linear else self._full_mixer(u)
        # the stream after the mixer's output product
        x = product(x + mix.astype(x.dtype), RESIDUAL, (
            self.value_heads * self.value_dim if self.linear
            else self.num_heads * self.head_dim))
        with jax.named_scope(_names.SCOPE_LM_NORM):
            m = self._norm("norm_moe")(x)
        x, load, rows = sparse_feed_forward(
            self, x, m, expert_width=self.expert_width,
            num_experts=self.num_experts, top_k=self.top_k, held=self.held,
            activation=jax.nn.silu, dtype=self.dtype,
            shared_width=self.shared_width, shared_sigmoid=True)
        return x, (load, rows)


class GatedDeltaDecoder(fnn.Module):
    """Decoder-only LM over ``GatedDeltaDecoderBlock``s: layer ``l`` has
    softmax attention iff ``(l + 1) % full_interval == 0``, else the delta
    mixer. Returns ``(logits [B, T, vocab] float32, {"moe_load": [layers,
    held], "moe_rows": [layers]} float32)``, what each layer measured of its
    routing; with ``targets`` the mean next-token loss stands where the
    logits do (``lm_head.VocabHead``). Its loss for the engine is
    ``models.make_moe_lm_loss_fn``'s and its state ``init_moe_state``'s,
    which ask ``expert_layers``, ``selected_layers`` and ``held`` of a
    model. The experts and the vocabulary given are those this device holds
    (the module's docstring: held by share)."""

    vocab_size: int = 256
    num_layers: int = 4
    d_model: int = 128
    full_interval: int = 4
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 32
    rotary_dim: int = 8
    key_heads: int = 2
    value_heads: int = 4
    key_dim: int = 16
    value_dim: int = 16
    expert_width: int = 64
    shared_width: int = 64
    num_experts: int = 8
    top_k: int = 2
    held: Sequence[int] = tuple(range(8))
    conv_width: int = 4
    chunk: int = 64
    rope_theta: float = 1e7
    norm_eps: float = 1e-6
    attn_block: int = 1024
    remat: bool = False  # recompute each block in backward, but for what
    #                      its attention's forward kernels kept and the
    #                      products' results the step has room for:
    #                      ``recomputed``, ``products_kept``
    dtype: Any = jnp.float32

    selected_layers = 0  # no layer selects its keys
    expert_bias = False  # ... and no router takes a bias

    def is_linear(self, i: int) -> bool:
        return (i + 1) % self.full_interval != 0

    @property
    def expert_layers(self) -> int:
        return self.num_layers

    @fnn.compact
    def __call__(self, tokens, targets=None):
        batch, t = tokens.shape
        blocks = [
            dict(linear=self.is_linear(i), num_heads=self.num_heads,
                 num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
                 rotary_dim=self.rotary_dim, key_heads=self.key_heads,
                 value_heads=self.value_heads, key_dim=self.key_dim,
                 value_dim=self.value_dim, expert_width=self.expert_width,
                 shared_width=self.shared_width,
                 num_experts=self.num_experts, top_k=self.top_k,
                 held=tuple(self.held), conv_width=self.conv_width,
                 chunk=self.chunk, rope_theta=self.rope_theta,
                 norm_eps=self.norm_eps, attn_block=self.attn_block,
                 dtype=self.dtype,
                 name=f"GatedDeltaDecoderBlock_{i}")  # with and without remat
            for i in range(self.num_layers)]
        block_cls = GatedDeltaDecoderBlock
        if self.remat:
            # before the attention calls are counted: it traces the blocks
            block_cls = recomputed(GatedDeltaDecoderBlock, keep=products_kept(
                self, GatedDeltaDecoderBlock, blocks,
                jax.ShapeDtypeStruct((batch, t, self.d_model), self.dtype),
                self.vocab_size))
        note_expert_layers(
            tokens.size, self.top_k, self.num_layers, len(self.held))
        note_attention_step()  # each full layer's call below counts itself
        linear = sum(self.is_linear(i) for i in range(self.num_layers))
        note_gdn_step(linear, batch, -(-t // self.chunk))
        note_conv_step(
            linear, (batch, t, 2 * self.key_heads * self.key_dim
                     + self.value_heads * self.value_dim),
            self.dtype, self.conv_width)
        with jax.named_scope(_names.SCOPE_LM_EMBED):
            x = TokenEmbed(
                self.vocab_size, self.d_model, dtype=self.dtype, name="embed"
            )(tokens)
        routing = []
        for block in blocks:
            x, measured = block_cls(**block)(x)
            routing.append(measured)
        with jax.named_scope(_names.SCOPE_LM_NORM):
            x = ZeroCentredRMSNorm(epsilon=self.norm_eps, name="norm")(x)
        logits = VocabHead(
            self.vocab_size, use_bias=False, dtype=jnp.float32,
            name="head")(x, targets)
        load, rows = (jnp.stack(a) for a in zip(*routing))
        return logits, {"moe_load": load, "moe_rows": rows}
