"""keye-vl-2-30b-a3b: one chip's share of Keye-VL-2.0-30B-A3B's language
model through the program's ``models.MoEDecoder`` (RMSNorm, 32 query to 4
KV heads with an RMSNorm a head, rotary position, attention over the 2,048
keys a learned indexer selects for each query, the router read after the
second norm, 16 of 128 SwiGLU experts held, 8 a token, nothing dropped;
the indexer trained beside the model by its own loss).

What the harness needs of a configuration: ``build(cfg) -> Built``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import flops, selected_decoder_flops, weights
from benchmark.configs import Built


def build(cfg):
    from torchmpi_tpu.models import (
        MoEDecoder,
        init_moe_state,
        make_moe_lm_loss_fn,
    )

    m, sa = cfg["model"], cfg["sa_config"]
    seq, vocab = cfg["sequence_length"], cfg["vocab_size"]
    held = tuple(m["experts_held"])
    if not len(held) == cfg["num_experts"] == cfg["num_local_experts"]:
        raise ValueError(
            f"experts_held names {len(held)} experts, num_experts says "
            f"{cfg['num_experts']}, num_local_experts "
            f"{cfg['num_local_experts']}")
    if cfg["hidden_act"] != "silu" or sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the layer is written for SiLU and one index key "
                         "head")
    model = MoEDecoder(
        vocab_size=vocab, num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        expert_width=cfg["moe_intermediate_size"],
        num_experts=m["router_outputs"], top_k=cfg["num_experts_per_tok"],
        held=held, window_layout=(0,), rope_layout=(1,),
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        attn_block=m["attention_block"], activation=jax.nn.silu,
        router_after_norm=True, qk_norm=True, selected_layout=(1,),
        index_top_k=sa["topk"], index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"], remat=cfg["remat"],
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
        if parts[-1] == "bias":
            return jnp.zeros(shape, jnp.float32)
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
            selected_decoder_flops.selected_decoder_forward_flops(
                seq, cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"],
                cfg["moe_intermediate_size"], m["router_outputs"],
                cfg["num_experts_per_tok"], len(held), vocab,
                cfg["num_hidden_layers"], sa["indexer_num_heads"],
                sa["indexer_head_dim"], sa["topk"],
            )),
        input_dtype=None,
        loss_must_fall=False,
    )
