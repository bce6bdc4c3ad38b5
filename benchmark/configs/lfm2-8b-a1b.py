"""lfm2-8b-a1b: one chip's share of LFM2-8B-A1B through the program's
``models.MoEDecoder`` (RMSNorm; by layer a gated short convolution of 3 taps
or attention of 32 query heads of 64 to 8 KV heads with a norm on each query
and key head and the whole head rotated; a leading layer whose feed-forward
is dense; then a router over 32 that chooses 4 a token by its sigmoid scores
plus a bias and weighs by the bare scores, 8 of the 32 SwiGLU experts held
(each layer's group at the mean expected load), nothing dropped; the head is
the embedding's table).

What the harness needs of a configuration: ``build(cfg) -> Built``.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import flops, sconv_decoder_flops, weights
from benchmark.configs import Built

# the projections that write into the residual stream
RESIDUAL = ("out_proj", "o", "mlp_down")
# as ``laguna-s-2-1.py``'s: the scale, in logits, of the expected load's
# soft choice, and the most frequent ids it is read from
SOFT = 0.03
PROBED = 1024


def held_at_mean_load(cfg, params):
    """``params`` with each router's columns turned by a whole number of
    chips' shares, so that the experts held here (0 to ``n - 1``) are, of
    the deployment's groups of ``n`` consecutive experts, the one expected
    the load nearest the mean share while the biases are 0
    (``laguna-s-2-1.py`` ``held_at_mean_load`` says why and how: with
    seeded weights a token's experts follow its id, under Zipf a few dozen
    ids are most of the tokens, and which group they load is a lottery
    drawn once a seed; the expectation is continuous in the weights so
    that the programs that trace ``state_at`` agree). The ``PROBED`` most
    frequent ids go through the leading dense feed-forward by themselves;
    what the mixers and the held experts add to a token's own vector is
    left out."""
    m = cfg["model"]
    n, k = len(m["experts_held"]), cfg["num_experts_per_tok"]
    tokens = cfg["sequence_length"] * cfg["per_chip_batch"]
    types = min(PROBED, cfg["vocab_size"])
    zipf = 1.0 / np.arange(1, cfg["vocab_size"] + 1)
    count = jnp.asarray(tokens * zipf[:types] / zipf.sum(), jnp.float32)
    dot = lambda a, b: jnp.dot(  # noqa: E731
        a, b, precision=jax.lax.Precision.HIGHEST)
    h, turned = params["embed"]["embedding"][:types], dict(params)
    for i in range(cfg["num_hidden_layers"]):
        name = f"MoEDecoderBlock_{i}"
        block = params[name]
        x = h * jax.lax.rsqrt(
            jnp.mean(h * h, axis=-1, keepdims=True) + cfg["norm_eps"]
        ) * block["norm_moe"]["scale"]
        if "router" not in block:
            h = h + dot(
                jax.nn.silu(dot(x, block["mlp_gate"]["kernel"]))
                * dot(x, block["mlp_up"]["kernel"]),
                block["mlp_down"]["kernel"])
            continue
        logits = dot(x, block["router"]["kernel"])
        edge = jnp.mean(
            jax.lax.top_k(logits, k + 1)[0][:, k - 1:], axis=-1,
            keepdims=True)
        load = jnp.sum(
            count[:, None] * jax.nn.sigmoid((logits - edge) / SOFT),
            axis=0).reshape(-1, n).sum(axis=-1)
        group = jnp.argmin(jnp.abs(load - jnp.mean(load)))
        turned[name] = {**block, "router": {"kernel": jnp.roll(
            block["router"]["kernel"], -n * group, axis=1)}}
    return turned


def build(cfg):
    from torchmpi_tpu.models import (
        MoEDecoder,
        init_moe_state,
        make_moe_lm_loss_fn,
    )
    from torchmpi_tpu.parallel import biased_sigmoid_route_weights

    m, init = cfg["model"], cfg["init"]
    seq, vocab = cfg["sequence_length"], cfg["vocab_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    kinds = cfg["layer_types"]
    held = tuple(m["experts_held"])
    heads = cfg["num_attention_heads"]
    if not (held == tuple(range(cfg["num_experts"]))
            and m["router_outputs"] % len(held) == 0
            and len(kinds) == layers and 0 <= dense < layers
            and set(kinds) <= {"conv", "full_attention"}
            and cfg["hidden_size"] % heads == 0):
        raise ValueError("experts_held, num_experts and layer_types do not "
                         "describe one cut")
    if not (cfg["use_expert_bias"] and cfg["norm_topk_prob"]
            and not cfg["conv_bias"]
            and seq <= cfg["max_position_embeddings"]):
        raise ValueError(
            "the layer is written for a router that chooses by its scores "
            "plus a bias and weighs by the bare scores normalised, a "
            "convolution without a bias and a sequence the config's "
            "positions hold")
    model = MoEDecoder(
        vocab_size=vocab, num_layers=layers, d_model=cfg["hidden_size"],
        num_heads=heads, num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // heads,
        expert_width=cfg["moe_intermediate_size"],
        num_experts=m["router_outputs"], top_k=cfg["num_experts_per_tok"],
        held=held, window_layout=(0,), rope_layout=(1,),
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["norm_eps"],
        attn_block=m["attention_block"], activation=jax.nn.silu,
        router_after_norm=True, qk_norm=True,
        route_weights=functools.partial(
            biased_sigmoid_route_weights,
            scale=float(cfg["routed_scaling_factor"]),
            eps=m["route_epsilon"]),
        expert_bias=True, bias_update_rate=m["bias_update_rate"],
        dense_layers=dense, dense_width=cfg["intermediate_size"],
        conv_layout=tuple(int(k == "conv") for k in kinds),
        conv_taps=cfg["conv_L_cache"], tied_head=True,
        remat=cfg["remat"], dtype=jnp.dtype(cfg["compute_dtype"]),
    )
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)
        )
    )["params"]

    def init_leaf(name, shape, key):
        parts = name.split("/")
        leaf = parts[-1]
        if leaf == "scale":
            return jnp.ones(shape, jnp.float32)
        if leaf == "conv_kernel":
            bound = 1.0 / math.sqrt(shape[0])
            return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        if leaf == "embedding":
            return weights.normal(key, shape, init["embedding_std"])
        if leaf == "experts_down" or parts[-2] in RESIDUAL:
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
        state_at=lambda key: (
            held_at_mean_load(cfg, make_tree(key)), init_moe_state(model)),
        make_data=make_data,
        # Adam's first moment; after one step, (1 - b1) times the gradient
        first_moment=lambda opt_state: opt_state[0].mu,
        flops_per_sample=flops.train_flops(
            sconv_decoder_flops.sconv_decoder_forward_flops(
                seq, cfg["hidden_size"], kinds, cfg["conv_L_cache"], heads,
                cfg["num_key_value_heads"], cfg["hidden_size"] // heads,
                dense, cfg["intermediate_size"],
                cfg["moe_intermediate_size"], m["router_outputs"],
                cfg["num_experts_per_tok"], len(held), vocab,
            )),
        input_dtype=None,
        loss_must_fall=False,
    )
