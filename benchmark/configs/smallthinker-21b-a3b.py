"""smallthinker-21b-a3b: one chip's share of SmallThinker-21BA3B-Instruct
through the program's ``models.MoEDecoder`` (RMSNorm, 28 query to 4 KV
heads, one full-attention layer without position then three
sliding-window layers with rotary position, the router read before
attention, 8 of 64 ReGLU experts held, 6 a token, nothing dropped).

What the harness needs of a configuration: ``build(cfg) -> Built``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import decoder_flops, flops, weights
from benchmark.configs import Built


def windows_of(cfg):
    """One entry a layer: None (full attention) or the window."""
    layout = cfg["sliding_window_layout"]
    return [cfg["sliding_window_size"] if layout[i % len(layout)] else None
            for i in range(cfg["num_hidden_layers"])]


def build(cfg):
    from torchmpi_tpu.models import (
        MoEDecoder,
        init_moe_state,
        make_moe_lm_loss_fn,
    )

    m = cfg["model"]
    seq, vocab = cfg["sequence_length"], cfg["vocab_size"]
    held = tuple(m["experts_held"])
    if len(held) != cfg["moe_num_primary_experts"]:
        raise ValueError(
            f"experts_held names {len(held)} experts, "
            f"moe_num_primary_experts says {cfg['moe_num_primary_experts']}")
    model = MoEDecoder(
        vocab_size=vocab, num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        expert_width=cfg["moe_ffn_hidden_size"],
        num_experts=m["router_outputs"],
        top_k=cfg["moe_num_active_primary_experts"], held=held,
        window=cfg["sliding_window_size"],
        window_layout=tuple(cfg["sliding_window_layout"]),
        rope_layout=tuple(cfg["rope_layout"]),
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        attn_block=m["attention_block"], remat=cfg["remat"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
    )
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)
        )
    )["params"]

    init = cfg["init"]

    def init_leaf(name, shape, key):
        parts = name.split("/")
        if parts[-1] == "scale":
            return jnp.ones(shape, jnp.float32)
        if parts[-1] == "embedding":
            return weights.normal(key, shape, init["embedding_std"])
        if parts[-1] == "experts_down" or parts[-2] == "o":
            # the two projections that write into the residual stream
            return weights.normal(key, shape, init["residual_std"])
        return weights.normal(key, shape, init["std"])

    make_tree = weights.seeded_tree(shapes, init_leaf)

    def make_data(seed, n):
        # Zipf with exponent 1 over the slice's ids, by the inverse of the
        # cumulative distribution: id 0 is the most frequent token
        rng = np.random.default_rng([int(seed), 1])
        cdf = np.cumsum(1.0 / np.arange(1, vocab + 1))
        toks = np.searchsorted(
            cdf / cdf[-1], rng.random((n, seq + 1)), side="right"
        ).clip(max=vocab - 1).astype(np.int32)
        return (np.ascontiguousarray(toks[:, :-1]),
                np.ascontiguousarray(toks[:, 1:]))

    opt = cfg["optimizer"]
    return Built(
        loss_fn=make_moe_lm_loss_fn(model),
        optimizer=optax.adamw(
            opt["learning_rate"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"],
        ),
        state_at=lambda key: (make_tree(key), init_moe_state(model)),
        make_data=make_data,
        # Adam's first moment; after one step, (1 - b1) times the gradient
        first_moment=lambda opt_state: opt_state[0].mu,
        flops_per_sample=flops.train_flops(
            decoder_flops.moe_decoder_forward_flops(
                seq, cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"],
                cfg["moe_ffn_hidden_size"], m["router_outputs"],
                cfg["moe_num_active_primary_experts"], len(held), vocab,
                windows_of(cfg),
            )),
        input_dtype=None,
        loss_must_fall=False,
    )
