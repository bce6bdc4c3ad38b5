"""A hybrid decoder: in every block a state-space mixer (Mamba-2) **beside**
grouped-head attention, both reading one norm and their outputs summed
before the residual, then a dense gated feed-forward; scalar multipliers on
the embedding, the logits, the keys, each mixer's input and output, the five
parts of the mixer's input projection and the feed-forward's gate and
output (the Falcon-H1 family's).

A sibling of ``models/decoder.py``'s block, not more fields on it: that
block is one mixer then a feed-forward of experts, with a router in every
model; this one has two mixers side by side, no router and a multiplier at
every seam. What the two share is called, not copied: attention is
``parallel.ring_attention.blocked_self_attention``, the rotation
``lm.rotary``, the loss ``lm_head.VocabHead``'s through
``lm.make_lm_loss_fn``, the scopes ``telemetry.names``'; the
mixer's sequence operations are ``parallel.ssm``'s.

One layer, input ``h`` ``[t, d]``, the mixer's ``H`` heads of ``P`` in ``G``
groups with states of ``N``, ``m`` the multipliers: ``a = RMSNorm(h)``.
Mixer: ``[z | x | B | C | dt] = (m.ssm_in a) W_in``, the parts times
``m.ssm[0..4]``; ``[x | B | C] <- silu(conv(x | B | C))``, causal, depthwise,
with a bias; ``delta = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, a head;
``y`` the selective scan of ``parallel/ssm.py`` with ``D x`` added; ``y <-
RMSNorm by groups(y * silu(z))`` times a weight; ``mix = m.ssm_out (y
W_out)``. Attention: ``q = a' W_q``, ``k = m.key (a' W_k)``, ``v = a' W_v``
with ``a' = m.attention_in a``; rotary over the whole head; causal softmax at
``1 / sqrt(head_dim)``; ``att = m.attention_out (concat(o) W_o)``. ``h' = h +
mix + att``; ``f = RMSNorm(h')``; ``out = h' + m.mlp[1] ((silu(m.mlp[0] (f
W_g)) * (f W_u)) W_d)``. The model: the embedding times ``m.embedding``, the
layers, RMSNorm, logits ``m.lm_head (x W_head)``. No biases but the
convolution's.

**A layer held by share** (``models/decoder.py``'s sense): the query and KV
heads, the mixer's heads with their groups' ``B`` and ``C`` whole, and the
feed-forward's columns given are those this device holds; ``W_in``'s
columns are ``[z | x | B | C | dt]`` of the held heads and groups, ``W_out``,
``W_o`` and ``W_d`` the matching rows. What the layer adds to the residual
stream is this device's part of three sums, which are not made here. The
mixer's norm is over the channels held of each group; where a group's heads
lie on several devices, ``axis_name`` names the axis over which its mean
square is a ``psum`` (``parallel.ssm.gated_group_norm``).

Parameters are float32; the products run in ``dtype``; the norms, the
convolution, ``delta``, the decays and the state carried between chunks in
float32.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as fnn
import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.ring_attention import (
    blocked_self_attention,
    note_attention_step,
)
from ..parallel.ssm import (
    causal_conv1d_silu,
    gated_group_norm,
    note_conv_step,
    note_ssm_step,
    ssd_chunked_scan,
)
from ..telemetry import names as _names
from .embedding import TokenEmbed
from .lm import (
    a_log_init,
    dt_bias_init,
    products_kept,
    recomputed,
    rotary,
)
from .lm_head import VocabHead


class Multipliers(NamedTuple):
    """The family's scalar multipliers (muP's), by the config's keys."""

    embedding: float = 1.0
    lm_head: float = 1.0
    key: float = 1.0
    attention_in: float = 1.0
    attention_out: float = 1.0
    ssm_in: float = 1.0
    ssm_out: float = 1.0
    ssm: Tuple[float, ...] = (1.0,) * 5  # on z, x, B, C, dt of W_in's output
    mlp: Tuple[float, float] = (1.0, 1.0)  # on the gate's product, the output


def _uniform_init(bound):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


class HybridDecoderBlock(fnn.Module):
    num_heads: int       # query heads held here
    num_kv_heads: int
    head_dim: int
    ssm_heads: int       # the mixer's heads held here
    ssm_head_dim: int
    ssm_groups: int      # the groups of B and C those heads read, whole
    ssm_state: int
    mlp_width: int       # the feed-forward's columns held here
    multipliers: Multipliers = Multipliers()
    conv_width: int = 4
    chunk: int = 128
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    attn_block: int = 1024
    axis_name: Optional[str] = None  # the mixer's norm: see the module
    dtype: Any = jnp.float32

    def _dense(self, n, name):
        return fnn.Dense(n, use_bias=False, dtype=self.dtype, name=name)

    def _mixer(self, a):
        """``mix`` from the normed input ``a`` ``[b, t, d]`` float32."""
        b, t, d = a.shape
        m, f32 = self.multipliers, jnp.float32
        inner = self.ssm_heads * self.ssm_head_dim
        bc = self.ssm_groups * self.ssm_state
        sizes = (inner, inner, bc, bc, self.ssm_heads)
        with jax.named_scope(_names.SCOPE_SSM_PROJ):
            proj = self._dense(sum(sizes), "ssm_in")(
                (m.ssm_in * a).astype(self.dtype))
            proj = proj.astype(f32) * np.repeat(
                np.asarray(m.ssm, np.float32), sizes)
            z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * bc], axis=-1)
        with jax.named_scope(_names.SCOPE_SSM_CONV):
            # uniform within 1 / sqrt(taps), a depthwise kernel's fan-in
            taps = _uniform_init(1.0 / math.sqrt(self.conv_width))
            xbc = causal_conv1d_silu(
                xbc,
                self.param("conv_kernel", taps,
                           (self.conv_width, inner + 2 * bc), f32),
                self.param("conv_bias", taps, (inner + 2 * bc,), f32))
        with jax.named_scope(_names.SCOPE_SSM_SCAN):
            x, b_, c_ = jnp.split(xbc, [inner, inner + bc], axis=-1)
            delta = jax.nn.softplus(dt + self.param(
                "dt_bias", dt_bias_init, (self.ssm_heads,), f32))
            y = ssd_chunked_scan(
                x.reshape(b, t, self.ssm_heads, self.ssm_head_dim), delta,
                -jnp.exp(self.param(
                    "A_log", a_log_init, (self.ssm_heads,), f32)),
                b_.reshape(b, t, self.ssm_groups, self.ssm_state),
                c_.reshape(b, t, self.ssm_groups, self.ssm_state),
                self.param("D", fnn.initializers.ones, (self.ssm_heads,), f32),
                chunk=self.chunk, dtype=self.dtype)
        with jax.named_scope(_names.SCOPE_SSM_GATE):
            y = gated_group_norm(
                y.reshape(b, t, inner), z,
                self.param("ssm_norm", fnn.initializers.ones, (inner,), f32),
                self.ssm_groups, self.norm_eps, self.axis_name)
        with jax.named_scope(_names.SCOPE_SSM_PROJ):
            return m.ssm_out * self._dense(d, "ssm_out")(
                y.astype(self.dtype))

    def _attention(self, a):
        """``att`` from the normed input ``a`` ``[b, t, d]`` float32."""
        b, t, d = a.shape
        m, dense = self.multipliers, self._dense
        with jax.named_scope(_names.SCOPE_ATTN_PROJ):
            a = (m.attention_in * a).astype(self.dtype)
            q = dense(self.num_heads * self.head_dim, "q")(a)
            k = m.key * dense(self.num_kv_heads * self.head_dim, "k")(a)
            v = dense(self.num_kv_heads * self.head_dim, "v")(a)
        # the reshapes stand under no scope, as in models/decoder.py
        q = q.reshape(b, t, self.num_heads, self.head_dim)
        k = k.reshape(b, t, self.num_kv_heads, self.head_dim)
        v = v.reshape(b, t, self.num_kv_heads, self.head_dim)
        with jax.named_scope(_names.SCOPE_ATTN_FULL):
            attn = blocked_self_attention(
                rotary(q, self.rope_theta), rotary(k, self.rope_theta), v,
                block=self.attn_block)
        attn = attn.reshape(b, t, -1)
        with jax.named_scope(_names.SCOPE_ATTN_PROJ):
            return m.attention_out * dense(d, "o")(attn)

    @fnn.compact
    def __call__(self, x):
        # x: [B, T, D]
        m, d, dense = self.multipliers, x.shape[-1], self._dense
        norm = lambda name: fnn.RMSNorm(  # noqa: E731
            epsilon=self.norm_eps, dtype=jnp.float32, name=name)
        with jax.named_scope(_names.SCOPE_LM_NORM):
            a = norm("norm_mix")(x)
        x = x + (self._mixer(a) + self._attention(a)).astype(x.dtype)
        with jax.named_scope(_names.SCOPE_LM_NORM):
            f = norm("norm_mlp")(x).astype(self.dtype)
        with jax.named_scope(_names.SCOPE_LM_MLP):
            gate = jax.nn.silu(m.mlp[0] * dense(self.mlp_width, "mlp_gate")(f))
            return x + (m.mlp[1] * dense(d, "mlp_down")(
                gate * dense(self.mlp_width, "mlp_up")(f))).astype(x.dtype)


class HybridDecoder(fnn.Module):
    """Decoder-only LM over ``HybridDecoderBlock``s, every layer the same.
    Returns the logits ``[B, T, vocab]`` float32, or with ``targets`` the
    mean next-token loss (``lm_head.VocabHead``): the model keeps no state,
    so its loss is ``models.make_lm_loss_fn``'s, as GPT-2's. The heads, the
    groups, the columns and the vocabulary given are those this device
    holds (the module's docstring: a layer held by share)."""

    vocab_size: int = 256
    num_layers: int = 4
    d_model: int = 128
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 32
    ssm_heads: int = 4
    ssm_head_dim: int = 32
    ssm_groups: int = 1
    ssm_state: int = 16
    mlp_width: int = 256
    multipliers: Multipliers = Multipliers()
    conv_width: int = 4
    chunk: int = 128
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    attn_block: int = 1024
    axis_name: Optional[str] = None
    remat: bool = False  # recompute each block in backward, but for what
    #                      its attention's forward kernel kept
    #                      (``recomputed``; this file's products bear no
    #                      name yet, so ``products_kept`` keeps none of them:
    #                      with the feed-forward's gate kept, 0.33 GiB, the
    #                      step for the described v5e held 0.97 GiB more:
    #                      PERF.md section 7, PR 47)
    dtype: Any = jnp.float32

    @fnn.compact
    def __call__(self, tokens, targets=None):
        m = self.multipliers
        batch, t = tokens.shape
        blocks = [
            dict(num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                 head_dim=self.head_dim, ssm_heads=self.ssm_heads,
                 ssm_head_dim=self.ssm_head_dim, ssm_groups=self.ssm_groups,
                 ssm_state=self.ssm_state, mlp_width=self.mlp_width,
                 multipliers=m, conv_width=self.conv_width, chunk=self.chunk,
                 rope_theta=self.rope_theta, norm_eps=self.norm_eps,
                 attn_block=self.attn_block, axis_name=self.axis_name,
                 dtype=self.dtype,
                 name=f"HybridDecoderBlock_{i}")  # with and without remat
            for i in range(self.num_layers)]
        block_cls = HybridDecoderBlock
        if self.remat:
            # before the attention calls are counted: it traces a block
            # (and sets the gauges: nothing named, nothing kept)
            block_cls = recomputed(HybridDecoderBlock, keep=products_kept(
                self, HybridDecoderBlock, blocks,
                jax.ShapeDtypeStruct((batch, t, self.d_model), self.dtype),
                self.vocab_size))
        note_attention_step()  # each layer's attention counts itself
        note_ssm_step(self.num_layers * self.ssm_heads,
                      self.num_layers * batch * -(-t // self.chunk))
        note_conv_step(
            self.num_layers,
            (batch, t, self.ssm_heads * self.ssm_head_dim
             + 2 * self.ssm_groups * self.ssm_state),
            jnp.float32, self.conv_width)
        with jax.named_scope(_names.SCOPE_LM_EMBED):
            x = (m.embedding * TokenEmbed(
                self.vocab_size, self.d_model, dtype=jnp.float32,
                name="embed")(tokens)).astype(self.dtype)
        for block in blocks:
            x = block_cls(**block)(x)
        with jax.named_scope(_names.SCOPE_LM_NORM):
            x = fnn.RMSNorm(
                epsilon=self.norm_eps, dtype=jnp.float32, name="norm")(x)
        return VocabHead(
            self.vocab_size, use_bias=False, dtype=jnp.float32,
            scale=m.lm_head, name="head")(x, targets)
