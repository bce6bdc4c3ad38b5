"""A retentive decoder: in every block **power retention** (degree 2,
``parallel/retention.py``; arXiv:2507.04239) where softmax attention stood,
read by grouped query heads, then a dense gated feed-forward; no layer has a
softmax at all. The skeleton is a grouped-query decoder's with an RMSNorm on
each query and key head (the Brumby family's, which keeps Qwen3's).

A sibling of ``models/decoder.py``'s and ``models/hybrid.py``'s blocks, not
more fields on either: the first has a router in every model, the second two
mixers from one norm and a multiplier at every seam, and both files' lowered
steps are pinned letter for letter. What the three share is called, not
copied: the rotation is ``lm.rotary``, the head norm ``fnn.RMSNorm`` as
``decoder.py`` spells it, the recomputation ``lm.recomputed``, the
loss ``lm_head.VocabHead``'s through ``lm.make_lm_loss_fn``,
the scopes ``telemetry.names``'; the sequence operation is
``parallel.retention.power_retention``.

One layer, input ``x`` ``[t, D]``, ``n`` query heads reading ``h = n //
group`` KV heads of ``d``: ``u = RMSNorm(x)``; ``q_n = rot(RMSNorm_d(u
W_q)_n)``, ``k_h = rot(RMSNorm_d(u W_k)_h)``, ``v_h = (u W_v)_h``, no bias;
``g_h = log sigmoid(u . w_h + b_h)``, float32, one number a position and KV
head, the gate's own bias the one bias of the layer; ``a_ij = exp(sum_{l =
j+1..i} g_l,h) (q_i,n . k_j,h / sqrt(d))^2`` for ``j <= i``; ``y_i,n = sum_j
a_ij v_j,h / (sum_j a_ij + eps)``; ``x' = x + concat(y) W_o``; ``r =
RMSNorm(x')``; ``out = x' + (silu(r W_g) * (r W_u)) W_d``. The model: the
embedding, the layers, RMSNorm, the logits ``x W_head``.

**A layer held by share** (``models/decoder.py``'s sense): the query and KV
heads with their gates and the feed-forward's columns given are those this
device holds, ``W_o`` and ``W_d`` the matching rows. What the layer adds to
the residual stream is this device's part of two sums, which are not made
here: one device makes no partial sum, and no code stands in for the others.

Parameters are float32; the products run in ``dtype``; the norms, the gate,
the decays, the carried state and the division in float32.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as fnn
import jax
import jax.numpy as jnp

from ..parallel.retention import note_retention_step, power_retention
from ..telemetry import names as _names
from .embedding import TokenEmbed
from .lm import MLP_GATE, MLP_UP, product, products_kept, recomputed, rotary
from .lm_head import VocabHead

# what a seeded gate lets through of the state, a position: ``1 - 1 / n``
# with ``n`` log-uniform between these, by head and layer
GATE_MEMORY = (64.0, 8192.0)


def _gate_bias_init(key, shape, dtype=jnp.float32):
    """``sigmoid(bias) = 1 - 1 / n``, ``n`` log-uniform in ``GATE_MEMORY``:
    a state that lasts some dozens to some thousands of positions. (A
    zero-mean gate forgets in two.)"""
    n = jnp.exp(jax.random.uniform(
        key, shape, dtype, *(math.log(v) for v in GATE_MEMORY)))
    return jnp.log(n - 1.0)


class RetentionDecoderBlock(fnn.Module):
    num_heads: int       # query heads held here
    num_kv_heads: int    # KV heads held here, each with its gate
    head_dim: int
    mlp_width: int       # the feed-forward's columns held here
    chunk: int = 256     # the program's, not the model's
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    eps: float = 1e-12   # beside the normaliser: a guard against 0 / 0 alone
    dtype: Any = jnp.float32

    @fnn.compact
    def __call__(self, x):
        # x: [B, T, D]
        b, t, d = x.shape
        f32 = jnp.float32
        dense = lambda n, name: fnn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        norm = lambda name: fnn.RMSNorm(  # noqa: E731
            epsilon=self.norm_eps, dtype=f32, name=name)
        with jax.named_scope(_names.SCOPE_LM_NORM):
            u = norm("norm_ret")(x)
        with jax.named_scope(_names.SCOPE_ATTN_PROJ):
            h = u.astype(self.dtype)
            q = dense(self.num_heads * self.head_dim, "q")(h)
            k = dense(self.num_kv_heads * self.head_dim, "k")(h)
            v = dense(self.num_kv_heads * self.head_dim, "v")(h)
        # the reshapes stand under no scope, as in models/decoder.py
        q = q.reshape(b, t, self.num_heads, self.head_dim)
        k = k.reshape(b, t, self.num_kv_heads, self.head_dim)
        v = v.reshape(b, t, self.num_kv_heads, self.head_dim)
        with jax.named_scope(_names.SCOPE_LM_NORM):
            # float32 on to the rotation: the operation rounds once
            q, k = norm("q_norm")(q), norm("k_norm")(k)
        with jax.named_scope(_names.SCOPE_RET_GATE):
            log_g = jax.nn.log_sigmoid(fnn.Dense(
                self.num_kv_heads, dtype=f32,
                precision=jax.lax.Precision.HIGHEST,
                kernel_init=fnn.initializers.normal(0.02 / math.sqrt(d)),
                bias_init=_gate_bias_init, name="gate")(u))
        with jax.named_scope(_names.SCOPE_RET_CHUNK):
            q, k = rotary(q, self.rope_theta), rotary(k, self.rope_theta)
        # opens the three retention scopes itself
        y = power_retention(q, k, v, log_g, chunk=self.chunk,
                            dtype=self.dtype, eps=self.eps)
        y = y.astype(self.dtype).reshape(b, t, -1)
        with jax.named_scope(_names.SCOPE_ATTN_PROJ):
            x = x + dense(d, "o")(y).astype(x.dtype)
        with jax.named_scope(_names.SCOPE_LM_NORM):
            r = norm("norm_mlp")(x).astype(self.dtype)
        with jax.named_scope(_names.SCOPE_LM_MLP):
            # before the SiLU, which is elementwise and fuses: made again
            gate = jax.nn.silu(
                product(dense(self.mlp_width, "mlp_gate")(r), MLP_GATE, d))
            return x + dense(d, "mlp_down")(
                gate * product(dense(self.mlp_width, "mlp_up")(r), MLP_UP, d)
            ).astype(x.dtype)


class RetentionDecoder(fnn.Module):
    """Decoder-only LM over ``RetentionDecoderBlock``s, every layer the
    same. Returns the logits ``[B, T, vocab]`` float32, or with ``targets``
    the mean next-token loss (``lm_head.VocabHead``): the model keeps no
    state, so its loss is ``models.make_lm_loss_fn``'s, as GPT-2's. The
    heads, the columns and the vocabulary given are those this device holds
    (the module's docstring: a layer held by share)."""

    vocab_size: int = 256
    num_layers: int = 4
    d_model: int = 128
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 32
    mlp_width: int = 256
    chunk: int = 256
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    eps: float = 1e-12
    remat: bool = False  # recompute each block in backward (``recomputed``:
    #                      all but the products' results the step has room for)
    dtype: Any = jnp.float32

    @fnn.compact
    def __call__(self, tokens, targets=None):
        batch, t = tokens.shape
        note_retention_step(self.num_layers, batch, self.num_kv_heads,
                            self.head_dim, -(-t // self.chunk))
        with jax.named_scope(_names.SCOPE_LM_EMBED):
            x = TokenEmbed(
                self.vocab_size, self.d_model, dtype=jnp.float32,
                name="embed")(tokens).astype(self.dtype)
        blocks = [
            dict(num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                 head_dim=self.head_dim, mlp_width=self.mlp_width,
                 chunk=self.chunk, rope_theta=self.rope_theta,
                 norm_eps=self.norm_eps, eps=self.eps, dtype=self.dtype,
                 name=f"RetentionDecoderBlock_{i}")  # with and without remat
            for i in range(self.num_layers)]
        block_cls = RetentionDecoderBlock
        if self.remat:
            block_cls = recomputed(RetentionDecoderBlock, keep=products_kept(
                self, RetentionDecoderBlock, blocks,
                jax.ShapeDtypeStruct(x.shape, x.dtype),
                self.vocab_size))
        for block in blocks:
            x = block_cls(**block)(x)
        with jax.named_scope(_names.SCOPE_LM_NORM):
            x = fnn.RMSNorm(
                epsilon=self.norm_eps, dtype=jnp.float32, name="norm")(x)
        return VocabHead(
            self.vocab_size, use_bias=False, dtype=jnp.float32,
            name="head")(x, targets)
