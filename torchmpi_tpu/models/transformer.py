"""Long-context causal transformer with ring-attention sequence parallelism.

New capability beyond the 2017 reference (SURVEY.md §5 marks long-context as
absent there): a decoder-only block stack whose attention runs over a
sequence axis sharded across devices via :func:`ring_self_attention` — the
sequence dimension never materialises on one chip, so context length scales
with the sp-axis size. MXU-friendly dims (multiples of 128 for model width).
On one device (``sp_axis=None``) the attention is
:func:`blocked_self_attention` over the whole causal prefix: no ``T x T``
tensor; the call chooses its execution (fused kernels or loops), the model
sets nothing.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as fnn
import jax
import jax.numpy as jnp

from ..parallel.ring_attention import (
    blocked_self_attention,
    note_attention_step,
    ring_self_attention,
)
from ..telemetry import names as _names
from .embedding import TokenEmbed
from .lm import MLP_GATE, QKV, RESIDUAL, product, products_kept, recomputed
from .lm_head import VocabHead


class RingAttentionBlock(fnn.Module):
    num_heads: int
    head_dim: int
    mlp_ratio: int = 4
    sp_axis: Optional[str] = None  # None = one shard: blocked attention
    sp_backend: str = "xla"  # 'xla' | 'pallas[_interpret][_bidir][_full]'
    dtype: Any = jnp.float32

    @fnn.compact
    def __call__(self, x):
        # x: [B, T_local, D]
        d_model = x.shape[-1]
        with jax.named_scope(_names.SCOPE_LM_NORM):
            h = fnn.LayerNorm(dtype=jnp.float32)(x)
        with jax.named_scope(_names.SCOPE_ATTN_PROJ):
            qkv = product(fnn.Dense(
                3 * self.num_heads * self.head_dim, dtype=self.dtype)(h),
                QKV, d_model)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        # the reshapes on either side of the attention stand under no scope,
        # as in models/decoder.py
        shape = x.shape[:2] + (self.num_heads, self.head_dim)
        q, k, v = (a.reshape(shape) for a in (q, k, v))
        # the decoders' name for the same thing: attention over the whole
        # causal prefix, no projection
        with jax.named_scope(_names.SCOPE_ATTN_FULL):
            if self.sp_axis is not None:
                attn = ring_self_attention(
                    q, k, v, axis=self.sp_axis, causal=True,
                    backend=self.sp_backend,
                )
            else:
                attn = blocked_self_attention(q, k, v)
        attn = attn.reshape(x.shape[:2] + (-1,))
        with jax.named_scope(_names.SCOPE_ATTN_PROJ):
            x = product(x + fnn.Dense(d_model, dtype=self.dtype)(attn),
                        RESIDUAL, attn.shape[-1])

        with jax.named_scope(_names.SCOPE_LM_NORM):
            h = fnn.LayerNorm(dtype=jnp.float32)(x)
        with jax.named_scope(_names.SCOPE_LM_MLP):
            # before the GELU, which is elementwise and fuses: made again
            h = product(fnn.Dense(
                self.mlp_ratio * d_model, dtype=self.dtype)(h),
                MLP_GATE, d_model)
            h = fnn.gelu(h)
            x = x + fnn.Dense(d_model, dtype=self.dtype)(h)
        return x


class LongContextTransformer(fnn.Module):
    """Decoder-only LM. With ``sp_axis`` set, call inside shard_map with the
    sequence dimension sharded over that axis; position embeddings use the
    *global* positions of the local shard."""

    vocab_size: int = 256
    num_layers: int = 2
    num_heads: int = 4
    head_dim: int = 32
    d_model: int = 128
    max_len: int = 4096
    sp_axis: Optional[str] = None
    sp_backend: str = "xla"  # ring-attention transport (see RingAttentionBlock)
    remat: bool = False  # recompute each block in backward (``recomputed``:
    #                      all but the attention kernels' output and lse and
    #                      the products' results the step has room for)
    dtype: Any = jnp.float32

    @fnn.compact
    def __call__(self, tokens, targets=None):
        # tokens: [B, T_local] int32; with ``targets`` the mean next-token
        # loss over the local positions, not the logits
        blocks = [
            dict(num_heads=self.num_heads, head_dim=self.head_dim,
                 sp_axis=self.sp_axis, sp_backend=self.sp_backend,
                 dtype=self.dtype, name=f"RingAttentionBlock_{i}")
            for i in range(self.num_layers)]
        block_cls = RingAttentionBlock
        if self.remat:
            # before the attention calls are counted: it traces a block
            block_cls = recomputed(RingAttentionBlock, keep=products_kept(
                self, RingAttentionBlock, blocks, jax.ShapeDtypeStruct(
                    tokens.shape + (self.d_model,), self.dtype),
                self.vocab_size))
        note_attention_step()
        t_local = tokens.shape[1]
        if self.sp_axis is not None:
            r = jax.lax.axis_index(self.sp_axis)
            pos = r * t_local + jnp.arange(t_local)
        else:
            pos = jnp.arange(t_local)
        with jax.named_scope(_names.SCOPE_LM_EMBED):
            # named as flax named them when both were ``fnn.Embed``
            x = TokenEmbed(
                self.vocab_size, self.d_model, dtype=self.dtype,
                name="Embed_0")(tokens)
            # the positions: ``t`` distinct sorted rows, summed over the
            # batch before they reach the table
            x = x + fnn.Embed(
                self.max_len, self.d_model, dtype=self.dtype,
                name="Embed_1")(pos)[None]
        # remat: drop each block's activations and recompute them during
        # backward — long-context HBM is dominated by per-layer
        # activations ([B, T, D] x layers), so this trades one extra
        # forward per block for an O(num_layers) -> O(1) activation
        # footprint (the standard long-sequence memory lever on TPU); the
        # fused attention kernels' output and log-sum-exp are kept
        # (``recomputed``: 2 x [B, T, D] bytes + 4 a head and position a
        # layer) and, where the step has the room, the products' results
        # (``products_kept``, above)
        for block in blocks:
            # explicit name: the remat wrapper would otherwise rename the
            # module path (Checkpoint...), making remat and non-remat
            # checkpoints incompatible — same params must drive both
            x = block_cls(**block)(x)
        with jax.named_scope(_names.SCOPE_LM_NORM):
            x = fnn.LayerNorm(dtype=jnp.float32)(x)
        # named as flax named it when it was ``fnn.Dense``
        return VocabHead(
            self.vocab_size, dtype=jnp.float32, name="Dense_0")(x, targets)
