"""brumby-14b: one chip's share of Brumby-14B-Base through the program's
``models.RetentionDecoder`` (Qwen3-14B's skeleton with power retention of
degree 2 where attention stood, in every layer, then a SwiGLU feed-forward):
1 of 8 KV heads with its 5 query heads and its gate, 2,176 of 17,408
feed-forward columns, an eighth of the vocabulary.

What the harness needs of a configuration: ``build(cfg) -> Built``.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import flops, retention_decoder_flops, weights
from benchmark.configs import Built


def build(cfg):
    from torchmpi_tpu.models import RetentionDecoder, make_lm_loss_fn

    m, init = cfg["model"], cfg["init"]
    seq, vocab = cfg["sequence_length"], cfg["vocab_size"]
    if not (m["retention_degree"] == 2 and cfg["hidden_act"] == "silu"
            and not cfg["attention_bias"] and cfg["rope_scaling"] is None
            and not cfg["use_sliding_window"]
            and cfg["sliding_window"] is None
            and not cfg["tie_word_embeddings"]
            and seq <= cfg["max_position_embeddings"]):
        raise ValueError(
            "the layer is written for power retention of degree 2 in every "
            "layer, a SiLU gate in the feed-forward, no bias but the "
            "retention gate's, plain rotary position, no window, an untied "
            "head and a sequence the config's positions hold")
    model = RetentionDecoder(
        vocab_size=vocab, num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mlp_width=m["dense_columns_held"], chunk=m["retention_chunk"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        eps=m["retention_eps"], remat=cfg["remat"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
    )
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)
        )
    )["params"]

    stds = {"embedding": init["embedding_std"], "o": init["residual_std"],
            "mlp_down": init["residual_std"],
            "gate": init["gate"]["weight_std"]}
    low, high = (math.log(n) for n in init["gate"]["memory"])

    def init_leaf(name, shape, key):
        parts = name.split("/")
        leaf = parts[-1]
        if leaf == "scale":
            return jnp.ones(shape, jnp.float32)
        if leaf == "bias":  # the gate's: sigmoid(bias) = 1 - 1 / n
            n = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, low, high))
            return jnp.log(n - 1.0)
        return weights.normal(key, shape, stds.get(
            "embedding" if leaf == "embedding" else parts[-2], init["std"]))

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
            retention_decoder_flops.retention_decoder_forward_flops(
                seq, cfg["hidden_size"], cfg["num_hidden_layers"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], m["dense_columns_held"], vocab)),
        input_dtype=None,
        loss_must_fall=False,
    )
