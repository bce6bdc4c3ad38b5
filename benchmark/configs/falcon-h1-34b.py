"""falcon-h1-34b: one chip's share of Falcon-H1-34B-Instruct through the
program's ``models.HybridDecoder`` (in every block a Mamba-2 mixer beside
grouped-head attention, both from one RMSNorm, then a SwiGLU feed-forward;
the family's twelve multipliers): 1 of 4 KV heads with its 5 query heads, 4
of the mixer's 32 heads with their group's ``B`` and ``C`` whole, 2,688 of
21,504 feed-forward columns, an eighth of the vocabulary.

What the harness needs of a configuration: ``build(cfg) -> Built``.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import flops, hybrid_decoder_flops, weights
from benchmark.configs import Built


def stds_of(cfg):
    """The standard deviation of every seeded matrix, by its name in a
    block or the model: ``init``'s (``smallthinker-21b-a3b``'s) divided by
    the multipliers the matrix's product meets, so that each product, as
    the next operation reads it, has the scale it has there (the file's
    ``assumed.weights``). ``ssm_in``'s is one number a column."""
    init, m = cfg["init"], cfg["ssm_multipliers"]
    std, residual = init["std"], init["residual_std"]
    heads, groups = cfg["mamba_n_heads"], cfg["mamba_n_groups"]
    inner, bc = heads * cfg["mamba_d_head"], groups * cfg["mamba_d_state"]
    attn_in = cfg["attention_in_multiplier"]
    return {
        "embedding": init["embedding_std"] / cfg["embedding_multiplier"],
        "head": std / cfg["lm_head_multiplier"],
        "q": std / attn_in, "v": std / attn_in,
        "k": std / (attn_in * cfg["key_multiplier"]),
        "o": residual / cfg["attention_out_multiplier"],
        "ssm_in": np.repeat(
            std / (cfg["ssm_in_multiplier"] * np.asarray(m, np.float32)),
            (inner, inner, bc, bc, heads)).astype(np.float32),
        "ssm_out": residual / cfg["ssm_out_multiplier"],
        "mlp_gate": std / cfg["mlp_multipliers"][0], "mlp_up": std,
        "mlp_down": residual / cfg["mlp_multipliers"][1],
    }


def build(cfg):
    from torchmpi_tpu.models import (
        HybridDecoder,
        Multipliers,
        make_lm_loss_fn,
    )

    m = cfg["model"]
    seq, vocab = cfg["sequence_length"], cfg["vocab_size"]
    heads, groups = cfg["mamba_n_heads"], cfg["mamba_n_groups"]
    if not (len(m["ssm_heads_held"]) == heads and groups == 1
            and cfg["num_key_value_heads"] == 1
            and cfg["hidden_act"] == "silu" and cfg["mamba_rms_norm"]
            and not cfg["mamba_norm_before_gate"] and cfg["mamba_conv_bias"]
            and not (cfg["attention_bias"] or cfg["mlp_bias"]
                     or cfg["mamba_proj_bias"] or cfg["projectors_bias"])
            and cfg["attn_layer_indices"] is None
            and cfg["rope_scaling"] is None
            and not cfg["tie_word_embeddings"]):
        raise ValueError(
            "the layer is written for one KV head and one group of mixer "
            "heads held, attention in every layer, a SiLU gate before the "
            "mixer's norm, no biases but the convolution's, plain rotary "
            "position and an untied head")
    model = HybridDecoder(
        vocab_size=vocab, num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ssm_heads=heads, ssm_head_dim=cfg["mamba_d_head"], ssm_groups=groups,
        ssm_state=cfg["mamba_d_state"], mlp_width=m["dense_columns_held"],
        multipliers=Multipliers(
            embedding=cfg["embedding_multiplier"],
            lm_head=cfg["lm_head_multiplier"], key=cfg["key_multiplier"],
            attention_in=cfg["attention_in_multiplier"],
            attention_out=cfg["attention_out_multiplier"],
            ssm_in=cfg["ssm_in_multiplier"],
            ssm_out=cfg["ssm_out_multiplier"],
            ssm=tuple(cfg["ssm_multipliers"]),
            mlp=tuple(cfg["mlp_multipliers"])),
        conv_width=cfg["mamba_d_conv"], chunk=cfg["mamba_chunk_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        attn_block=m["attention_block"], remat=cfg["remat"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
    )
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)
        )
    )["params"]

    stds, mixer = stds_of(cfg), cfg["init"]["mixer"]
    taps = 1.0 / math.sqrt(cfg["mamba_d_conv"])

    def init_leaf(name, shape, key):
        parts = name.split("/")
        leaf = parts[-1]
        if leaf in ("scale", "ssm_norm"):
            return jnp.ones(shape, jnp.float32)
        if leaf == "D":
            return jnp.full(shape, mixer["D"], jnp.float32)
        if leaf == "A_log":
            return jnp.log(jax.random.uniform(
                key, shape, jnp.float32, *mixer["A"]))
        if leaf == "dt_bias":  # the inverse of the softplus at delta
            low, high = (math.log(v) for v in mixer["delta"])
            delta = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, low, high))
            return delta + jnp.log(-jnp.expm1(-delta))
        if leaf in ("conv_kernel", "conv_bias"):
            return jax.random.uniform(key, shape, jnp.float32, -taps, taps)
        return weights.normal(key, shape, 1.0) * stds[
            "embedding" if leaf == "embedding" else parts[-2]]

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
        loss_fn=make_lm_loss_fn(model),
        optimizer=optax.adamw(
            opt["learning_rate"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"],
        ),
        state_at=lambda key: (make_tree(key), None),
        make_data=make_data,
        # Adam's first moment; after one step, (1 - b1) times the gradient
        first_moment=lambda opt_state: opt_state[0].mu,
        flops_per_sample=flops.train_flops(
            hybrid_decoder_flops.hybrid_decoder_forward_flops(
                seq, cfg["hidden_size"], cfg["num_hidden_layers"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], heads, cfg["mamba_d_head"], groups,
                cfg["mamba_d_state"], cfg["mamba_d_conv"],
                m["dense_columns_held"], vocab)),
        input_dtype=None,
        loss_must_fall=False,
    )
