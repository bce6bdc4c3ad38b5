"""qwen3-next-80b-a3b: one chip's share of Qwen3-Next-80B-A3B-Instruct
through the program's ``models.GatedDeltaDecoder`` (zero-centred RMSNorm;
three Gated DeltaNet layers, 16 key heads of 128 read by 32 value heads of
128 behind a convolution of 4, to one layer of softmax attention, 16 query
heads of 256 to 2 KV heads with an elementwise output gate and a quarter of
the head rotated; under each a router over 512, 16 of the 512 SwiGLU experts
held, 10 a token, nothing dropped, beside a sigmoid-gated shared expert).

What the harness needs of a configuration: ``build(cfg) -> Built``.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import deltanet_decoder_flops, flops, weights
from benchmark.configs import Built

# the projections that write into the residual stream
RESIDUAL = ("out", "o", "shared_down")


def build(cfg):
    from torchmpi_tpu.models import (
        GatedDeltaDecoder,
        init_moe_state,
        make_moe_lm_loss_fn,
    )

    m, init = cfg["model"], cfg["init"]
    seq, vocab = cfg["sequence_length"], cfg["vocab_size"]
    layers, interval = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    held = tuple(m["experts_held"])
    if not (held == tuple(range(cfg["num_experts"]))
            and m["router_outputs"] % len(held) == 0
            and layers % interval == 0):
        raise ValueError("experts_held, num_experts and the layers do not "
                         "describe one cut of whole periods")
    if not (cfg["hidden_act"] == "silu" and cfg["norm_topk_prob"]
            and cfg["decoder_sparse_step"] == 1
            and cfg["mlp_only_layers"] == []
            and cfg["rope_scaling"] is None
            and not cfg["use_sliding_window"]
            and not cfg["tie_word_embeddings"]
            and seq <= cfg["max_position_embeddings"]):
        raise ValueError(
            "the layer is written for SiLU, chosen weights that sum to 1, "
            "an expert layer in every layer, plain rotary position, no "
            "window, an untied head and a sequence the config's positions "
            "hold")
    model = GatedDeltaDecoder(
        vocab_size=vocab, num_layers=layers, d_model=cfg["hidden_size"],
        full_interval=interval, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rotary_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        key_heads=cfg["linear_num_key_heads"],
        value_heads=cfg["linear_num_value_heads"],
        key_dim=cfg["linear_key_head_dim"],
        value_dim=cfg["linear_value_head_dim"],
        expert_width=cfg["moe_intermediate_size"],
        shared_width=cfg["shared_expert_intermediate_size"],
        num_experts=m["router_outputs"], top_k=cfg["num_experts_per_tok"],
        held=held, conv_width=cfg["linear_conv_kernel_dim"],
        chunk=m["gdn_chunk"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"], attn_block=m["attention_block"],
        remat=cfg["remat"], dtype=jnp.dtype(cfg["compute_dtype"]),
    )
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)
        )
    )["params"]

    decay = init["decay"]
    uniform = jax.random.uniform

    def init_leaf(name, shape, key):
        parts = name.split("/")
        leaf = parts[-1]
        if leaf == "scale":  # a zero-centred norm's: the scale is 1 + this
            return jnp.zeros(shape, jnp.float32)
        if leaf == "out_norm":
            return jnp.ones(shape, jnp.float32)
        if leaf == "A_log":
            return jnp.log(uniform(key, shape, jnp.float32, *decay["A"]))
        if leaf == "dt_bias":  # the inverse of the softplus
            dt = jnp.exp(uniform(key, shape, jnp.float32, *(
                math.log(v) for v in decay["dt"])))
            return dt + jnp.log(-jnp.expm1(-dt))
        if leaf == "conv_kernel":
            bound = 1.0 / math.sqrt(shape[0])
            return uniform(key, shape, jnp.float32, -bound, bound)
        if leaf == "embedding":
            return weights.normal(key, shape, init["embedding_std"])
        if leaf == "experts_down" or parts[-2] in RESIDUAL:
            return weights.normal(key, shape, init["residual_std"])
        if parts[-2] == "in_ba":
            return weights.normal(key, shape, decay["ba_std"])
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
    linear = sum(model.is_linear(i) for i in range(layers))
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
            deltanet_decoder_flops.deltanet_decoder_forward_flops(
                seq, cfg["hidden_size"], linear, layers - linear,
                cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
                cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
                cfg["linear_conv_kernel_dim"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"],
                cfg["moe_intermediate_size"],
                cfg["shared_expert_intermediate_size"], m["router_outputs"],
                cfg["num_experts_per_tok"], len(held), vocab,
            )),
        input_dtype=None,
        loss_must_fall=False,
    )
