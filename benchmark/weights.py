"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program's ``model.init``: the
program is asked for the shapes only. The same call, with the same seed,
gives the plain reference its float32 copy after the window.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def path_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def seeded_tree(shapes, init_leaf):
    """``shapes``: a pytree of ShapeDtypeStruct. ``init_leaf(name, shape,
    key) -> float32 array``. Returns ``make(key) -> tree``, traceable: under
    one ``jax.jit`` it is one compiled program that makes every leaf, and
    inside a larger one XLA fuses each leaf into its use."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        return treedef.unflatten([
            init_leaf(path_name(p), s.shape, jax.random.fold_in(key, i))
            .astype(s.dtype)
            for i, (p, s) in enumerate(leaves)
        ])

    return make


def normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)
