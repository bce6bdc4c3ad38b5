"""gpt2-medium: GPT-2 medium's sizes through the program's
``models.LongContextTransformer`` (pre-LN, learned positions, tanh GELU).

What the harness needs of a configuration: ``build(cfg) -> Built``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import flops, weights
from benchmark.configs import Built


def build(cfg):
    from torchmpi_tpu.models import LongContextTransformer, make_lm_loss_fn

    m = cfg["model"]
    seq = cfg["sequence_length"]
    head_dim = m["n_embd"] // m["n_head"]
    model = LongContextTransformer(
        vocab_size=m["vocab_size"], num_layers=m["n_layer"],
        num_heads=m["n_head"], head_dim=head_dim, d_model=m["n_embd"],
        max_len=m["n_positions"], remat=cfg["remat"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
    )
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)
        )
    )["params"]
    std = m.get("initializer_range", 0.02)

    def init_leaf(name, shape, key):
        leaf = name.rsplit("/", 1)[-1]
        if leaf in ("kernel", "embedding"):
            return weights.normal(key, shape, std)
        if leaf == "scale":
            return jnp.ones(shape, jnp.float32)
        return jnp.zeros(shape, jnp.float32)

    make_tree = weights.seeded_tree(shapes, init_leaf)

    def make_data(seed, n):
        rng = np.random.default_rng([int(seed), 1])
        toks = rng.integers(0, m["vocab_size"], size=(n, seq + 1),
                            dtype=np.int32)
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
        flops_per_sample=flops.train_flops(flops.causal_lm_forward_flops(
            seq, m["n_embd"], m["n_layer"], m["n_head"], head_dim,
            m["vocab_size"],
        )),
        input_dtype=None,
        loss_must_fall=False,
    )
