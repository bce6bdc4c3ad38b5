"""laguna-s-2-1: one chip's share of Laguna-S-2.1 through the program's
``models.MoEDecoder`` (RMSNorm; one KV head of 8 with its 6 query heads in
the full-attention layers and its 9 in the sliding-window ones, a sigmoid
gate on each head; rotary position over half a head under YaRN's
frequencies or over the whole head, by layer kind; a leading layer whose
dense feed-forward holds 1,536 of 12,288 columns; then a shared expert
whole beside 8 of 256 SwiGLU experts, 10 a token by sigmoid scores
normalised and scaled, nothing dropped).

What the harness needs of a configuration: ``build(cfg) -> Built``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import flops, gated_decoder_flops, weights
from benchmark.configs import Built

# the projections that write into the residual stream
RESIDUAL = ("o", "shared_down", "mlp_down")
# the scale, in logits, of the expected load's soft choice: the estimate
# is nearest the routes the program's forward pass holds at 0.02 to 0.04
# (14 seeds at the cell's size on the CPU; a hard choice is a fifth worse)
SOFT = 0.03
# the most frequent ids the expected load is read from: three quarters of
# the tokens, and all but a thousandth of what the lottery varies by
PROBED = 1024


def held_at_mean_load(cfg, params):
    """``params`` with each router's columns turned by a whole number of
    chips' shares, so that the experts held here (0 to ``n - 1``) are, of
    the deployment's groups of ``n`` consecutive experts, the one expected
    the load nearest the mean share.

    With seeded weights a token's experts follow its id, and under Zipf a
    few dozen ids are most of the tokens: which group they load is a
    lottery drawn once a seed (a layer's held routes read 3.3 k to 8.9 k of
    an expected 5,120 over 14 seeds, and the step's time follows them). A
    deployment places its experts by measured load; this chip stands for
    one that got the mean. The load is expected from the weights and the
    token frequencies alone: the ``PROBED`` most frequent ids each go
    through the feed-forward sublayers by themselves (what attention and
    the held experts add to the stream is small beside a token's own
    vector, and is left out), and an expert is expected an id's tokens by
    how far its logit stands above or below the middle between the id's
    ``top_k``-th and next logit, on the scale by which a token's logits
    move with its context (``SOFT``).

    So the expectation is continuous in the weights. ``state_at`` is
    traced into more than one program (the harness's ``make_state`` and
    its ``_change``), the chip's compiler tiles the same product
    differently in each, and the logits differ in their last bit: a count
    of hard choices would now and then turn a layer differently in the
    two, and the run would be called incorrect."""
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
            jnp.mean(h * h, axis=-1, keepdims=True) + cfg["rms_norm_eps"]
        ) * block["norm_moe"]["scale"]
        part = "mlp"
        if "router" in block:
            part = "shared"
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
        h = h + dot(
            jax.nn.silu(dot(x, block[part + "_gate"]["kernel"]))
            * dot(x, block[part + "_up"]["kernel"]),
            block[part + "_down"]["kernel"])
    return turned


def windows_of(cfg):
    """One entry a layer: None (full attention) or the window."""
    return [cfg["sliding_window"] if kind == "sliding_attention" else None
            for kind in cfg["layer_types"]]


def build(cfg):
    from torchmpi_tpu.models import (
        MoEDecoder,
        Rotary,
        init_moe_state,
        make_moe_lm_loss_fn,
    )
    from torchmpi_tpu.parallel import sigmoid_route_weights

    m = cfg["model"]
    seq, vocab = cfg["sequence_length"], cfg["vocab_size"]
    layers, dense = cfg["num_hidden_layers"], len(cfg["mlp_only_layers"])
    held = tuple(m["experts_held"])
    heads = cfg["num_attention_heads_per_layer"]
    full = cfg["rope_parameters"]["full_attention"]
    sliding = cfg["rope_parameters"]["sliding_attention"]
    kinds = cfg["layer_types"]
    if not (held == tuple(range(cfg["num_experts"]))
            and m["router_outputs"] % len(held) == 0
            and len(kinds) == len(heads)
            == len(cfg["mlp_layer_types"]) == layers
            and heads[0] == cfg["num_attention_heads"]):
        raise ValueError("experts_held, num_experts and the per-layer lists "
                         "do not describe one cut")
    if (cfg["mlp_only_layers"] != list(range(dense))
            or cfg["mlp_layer_types"] != ["dense"] * dense
            + ["sparse"] * (layers - dense)
            or set(cfg["gating_types"]) != {"per_head"}
            or full["rope_type"] != "yarn"
            or sliding["rope_type"] != "default"
            or sliding["partial_rotary_factor"] != 1):
        raise ValueError("the layer is written for leading dense layers, a "
                         "gate on each head, YaRN in the full layers and "
                         "the whole head rotated in the sliding ones")
    model = MoEDecoder(
        vocab_size=vocab, num_layers=layers, d_model=cfg["hidden_size"],
        num_heads=tuple(heads), num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], expert_width=cfg["moe_intermediate_size"],
        num_experts=m["router_outputs"], top_k=cfg["num_experts_per_tok"],
        held=held, window=cfg["sliding_window"],
        window_layout=tuple(int(k == "sliding_attention") for k in kinds),
        rope_layout=(1,), rope_theta=float(sliding["rope_theta"]),
        rope_full=Rotary(
            theta=float(full["rope_theta"]),
            width=int(cfg["head_dim"] * full["partial_rotary_factor"]),
            factor=float(full["factor"]),
            original_positions=full["original_max_position_embeddings"],
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=full["attention_factor"]),
        norm_eps=cfg["rms_norm_eps"], attn_block=m["attention_block"],
        activation=jax.nn.silu, router_after_norm=True, head_gate=True,
        route_weights=sigmoid_route_weights(cfg["moe_routed_scaling_factor"]),
        shared_width=cfg["shared_expert_intermediate_size"],
        dense_layers=dense, dense_width=m["dense_columns_held"],
        remat=cfg["remat"], dtype=jnp.dtype(cfg["compute_dtype"]),
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
        if parts[-1] == "experts_down" or parts[-2] in RESIDUAL:
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
            gated_decoder_flops.gated_decoder_forward_flops(
                seq, cfg["hidden_size"], heads, cfg["num_key_value_heads"],
                cfg["head_dim"], windows_of(cfg), dense,
                m["dense_columns_held"], cfg["moe_intermediate_size"],
                cfg["shared_expert_intermediate_size"], m["router_outputs"],
                cfg["num_experts_per_tok"], len(held), vocab,
            )),
        input_dtype=None,
        loss_must_fall=False,
    )
