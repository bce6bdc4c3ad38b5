"""Selected attention's kernels, interpreted on the CPU, over three panels
of queries (sequences of 2,500 and 3,072): against the loops where the
selection is larger than a panel and where scores tie across a panel's edge
(``selected_attention_cases.kernels_are_the_loops``, whose shorter cases are
``tests/test_selected_attention_kernels.py``'s), and what a caller that
recomputes the layer keeps. A file of its own because its five cases take
the interpreter as long as that file's ten."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from selected_attention_cases import (
    index_kernel_name,
    inputs,
    interpreted,
    kernel_calls,
    kernels_are_the_loops,
    lowered_for_tpu,
    tied,
    weighed,
)
from torchmpi_tpu.parallel import (
    selected_attention as sa,
    selected_self_attention,
)


@pytest.mark.parametrize("t,hq,hkv,top_k,period", [
    (2500, 32, 4, 1500, 0),   # 3 panels; 4 groups of 8; top_k over a panel
    (2500, 2, 2, 600, 5),     # ties at the threshold across a panel's edge
], ids=["panels_4x8", "ties_panels"])
def test_the_kernels_are_the_loops_on_what_a_kernel_can_get_wrong(
        t, hq, hkv, top_k, period):
    kernels_are_the_loops(t, hq, hkv, top_k, period)


# -- what a recomputing caller keeps ----------------------------------------
_names_kept = jax.checkpoint_policies.save_only_these_names
KEPT = {
    "no_recomputation": jax.checkpoint_policies.everything_saveable,
    "saved": _names_kept(sa.SAVED),
}
REMAT_T, REMAT_TOP_K = 2500, 600  # 3 panels; ties across their edges


def test_remat_keeps_the_selection_and_changes_no_number():
    """A caller that recomputes the layer in backward and keeps nothing
    but ``SAVED`` (the panels of index scores among it: backward then
    masks with the very bits forward selected from), against one that
    keeps everything: the value and every gradient are EQUAL, not close,
    on the interpreted kernels. The inputs' scores tie across tile and
    panel edges."""
    args = tied(inputs(11, 1, REMAT_T, 2, 1, 128, 2, 64), 5)
    assert len(sa._panels(3072)) == 3
    (got, grads), (want, want_grads) = (
        jax.jit(jax.value_and_grad(jax.checkpoint(
            weighed(partial(interpreted, REMAT_TOP_K)),
            policy=KEPT[kept]), argnums=range(6)))(*args)
        for kept in ("saved", "no_recomputation"))
    np.testing.assert_array_equal(got, want)
    for g, w in zip(grads, want_grads):
        assert np.any(np.asarray(w))
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("kept", sorted(KEPT))
def test_the_index_scores_are_made_once(kept):
    """A selecting layer's forward and backward, lowered for a TPU:
    backward holds no ``tm_attn_index_scores`` call beyond the forward's
    (one a panel), whether the caller recomputes the layer and keeps
    ``SAVED`` or recomputes nothing. Every other kernel runs once too."""
    t, panels = 3072, 3
    shapes = [(1, t, 2, 128), (1, t, 1, 128), (1, t, 1, 128), (1, t, 2, 64),
              (1, t, 64), (1, t, 2)]
    layer = jax.checkpoint(
        weighed(partial(selected_self_attention, top_k=70)),
        policy=KEPT[kept])
    calls = kernel_calls(lowered_for_tpu(
        jax.grad(layer, argnums=range(6)),
        *[jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]))
    # the reader's name is the lowered program's, or this finds none
    assert calls.pop(index_kernel_name()) == panels
    assert calls == dict.fromkeys((
        "tm_attn_select_kth", "tm_attn_sparse_fwd",
        "tm_attn_sparse_mean_probabilities", "tm_attn_sparse_bwd",
        "tm_attn_index_grad_queries", "tm_attn_index_grad_keys"), panels)
