"""Expert parallelism (MoE) over a mesh axis.

The reference predates MoE entirely (SURVEY.md §2.3 marks EP absent); this
is a capability extension in the modern taxonomy, built TPU-first:

- each device along the ``ep`` mesh axis owns ONE expert's parameters and
  a shard of the tokens;
- top-k routing (k=1 Switch-style, k=2 the GShard default) with a fixed
  per-expert **capacity** keeps every shape static (XLA requirement):
  token t goes to its k highest-scoring experts unless an expert's
  capacity is exhausted, in which case that route is dropped (its output
  contribution is zero — the standard overflow rule; first choices queue
  before second choices);
- dispatch/combine are einsums against a boolean ``[T, E, C]`` dispatch
  tensor (the Mesh-TensorFlow formulation), and the cross-device exchange
  is a single ``lax.all_to_all`` each way — the ICI-native analog of the
  all-to-all EP traffic in modern MoE stacks;
- everything is differentiable: gradients flow through the gate values
  and the expert parameters (the dispatch mask is constant wrt inputs).

Two expert layers, for two layouts:

- :func:`moe_dispatch_combine`: ONE expert on each device of the axis,
  a fixed capacity, routes past it dropped, an ``all_to_all`` each way.
- :func:`moe_local_experts`: MANY experts held on this device, which is
  told which of all ``E`` they are; it routes every token over all ``E``,
  keeps every route to an expert it holds (no capacity, nothing dropped,
  no ``[T, E, C]`` tensor) and computes its own experts' part of the
  result by grouped matrix products. What the experts held elsewhere
  would add is not computed here: across devices that part arrives by an
  exchange this function does not make.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import telemetry as _telemetry
from ..telemetry import names as _names


def moe_dispatch_combine(
    x,
    router_logits,
    expert_fn: Callable,
    expert_params,
    axis: str = "ep",
    capacity: int | None = None,
    top_k: int = 1,
    renormalize: bool = True,
):
    """Route each token to its top-k experts across the ``axis`` devices.

    Parameters
    ----------
    x : ``[T, d]`` this device's token shard.
    router_logits : ``[T, E]`` routing scores (E = axis size).
    expert_fn : ``expert_fn(params, tokens[N, d]) -> [N, d]`` — THIS
        device's expert computation.
    expert_params : this device's expert parameter pytree.
    capacity : per-expert slots per source device (default:
        2 * ceil(k*T/E), the usual capacity-factor-2 headroom scaled by
        the routing multiplicity).
    top_k : experts per token. 1 = Switch-style; 2 = the GShard default.
        Capacity is charged in choice priority order: every token's first
        choice queues before any token's second choice, so under pressure
        primary routes survive and secondary routes drop first.
    renormalize : for ``top_k > 1``, rescale the selected gate
        probabilities to sum to 1 per token (GShard semantics). Ignored
        for ``top_k=1``, which keeps the raw softmax probability
        (Switch semantics, and round-2 behavior).

    Returns ``[T, d]`` combined outputs (dropped routes contribute zeros).
    """
    E = lax.axis_size(axis)
    T, d = x.shape
    k = top_k
    if not 1 <= k <= E:
        raise ValueError(f"top_k must be in [1, {E}], got {k}")
    if router_logits.shape != (T, E):
        raise ValueError(
            f"router_logits must be [T={T}, E={E}], got "
            f"{tuple(router_logits.shape)}"
        )
    C = capacity if capacity is not None else 2 * (-(-(k * T) // E))
    if C <= 0:
        raise ValueError(f"capacity must be positive, got {C}")

    gates = jax.nn.softmax(router_logits, axis=-1)  # [T, E]
    _, idxs = lax.top_k(router_logits, k)  # [T, k]
    onehots = jax.nn.one_hot(idxs, E, dtype=x.dtype)  # [T, k, E]
    gate_vals = jnp.einsum("te,tke->tk", gates, onehots)  # [T, k]
    if k > 1 and renormalize:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
        )

    # per-expert queue positions, choice-major: all first choices count
    # before any second choice (GShard's priority rule). Counted in int32,
    # NOT x.dtype: a bf16 cumsum cannot represent queue positions past 256
    # (257 rounds to 256), which would silently blend tokens into shared
    # capacity slots.
    oh_i = jax.nn.one_hot(idxs, E, dtype=jnp.int32)  # [T, k, E]
    oh_cm = oh_i.transpose(1, 0, 2).reshape(k * T, E)
    pos_cm = jnp.cumsum(oh_cm, axis=0) - oh_cm
    my_pos = (
        jnp.sum(pos_cm * oh_cm, axis=-1).reshape(k, T).T
    )  # [T, k] int32
    keep = (my_pos < C).astype(x.dtype)
    # per-choice dispatch [T, k, E, C]; slots are disjoint by construction
    disp_k = (
        onehots[:, :, :, None]
        * jax.nn.one_hot(my_pos, C, dtype=x.dtype)[:, :, None, :]
        * keep[:, :, None, None]
    )
    disp = jnp.sum(disp_k, axis=1)  # [T, E, C] dispatch mask
    comb = jnp.einsum("tkec,tk->tec", disp_k, gate_vals)  # gate-weighted

    # [E, C, d]: slot (e, c) holds the token bound for expert e
    expert_inputs = jnp.einsum("tec,td->ecd", disp, x)
    # exchange: dim 0 (expert) splits across devices, arrivals stack on a
    # new source dim -> [E_src, C, d] all bound for MY expert
    arrived = lax.all_to_all(
        expert_inputs, axis, split_axis=0, concat_axis=0, tiled=True
    )
    outs = expert_fn(expert_params, arrived.reshape(E * C, d)).reshape(
        E, C, d
    )
    # route results back to their source devices
    returned = lax.all_to_all(
        outs, axis, split_axis=0, concat_axis=0, tiled=True
    )
    # combine: scatter back to token order, gate-weighted per route
    return jnp.einsum("tec,ecd->td", comb, returned)


def moe_load_stats(router_logits, axis: str = "ep", top_k: int = 1):
    """(tokens_per_expert[E], aux_load_balance_loss) — the standard
    mean-gate x mean-assignment auxiliary loss that discourages expert
    collapse. ``tokens_per_expert`` counts every selected route (each
    token occupies capacity at k experts), but the aux loss uses the
    GShard dispatch fraction — FIRST choice only — for any ``top_k``, so
    its magnitude matches the standard formulation and load-balance
    coefficients tuned on GShard/Switch setups transfer unchanged."""
    E = lax.axis_size(axis)
    gates = jax.nn.softmax(router_logits, axis=-1)
    _, idxs = lax.top_k(router_logits, top_k)
    routes = jnp.sum(jax.nn.one_hot(idxs, E, dtype=gates.dtype), axis=1)
    first = jax.nn.one_hot(idxs[:, 0], E, dtype=gates.dtype)
    # global statistics across every device's token shard
    tokens_per_expert = lax.psum(jnp.sum(routes, axis=0), axis)
    me = lax.pmean(jnp.mean(gates, axis=0), axis)
    ce = lax.pmean(jnp.mean(first, axis=0), axis)
    return tokens_per_expert, E * jnp.sum(me * ce)


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """``x[perm]`` for a permutation ``perm`` of the rows whose inverse is
    known: backward is the gather ``g[inverse]``, not a scatter."""
    return x[perm]


_permute_rows.defvjp(
    lambda x, perm, inverse: (x[perm], (perm, inverse)),
    lambda saved, g: (g[saved[1]], None, None),
)


def compact_rows(routes: int, held: int, experts: int) -> int:
    """The rows of an expert layer's compact tier, from its shapes alone:
    twice the share of the ``routes`` that ``held`` of ``experts`` are
    expected to receive, rounded up to 512 rows (the tile of rows XLA's
    grouped product works through on the TPU). Where that is no fewer
    than the routes (half the experts held or more, or a few hundred
    routes in all) there is no compact tier: the layer has one execution,
    sized for every route."""
    return min(routes, -(-2 * routes * held // (experts * 512)) * 512)


def _own(held_row, a):
    """``a`` with the rows of no group set to zero, forward and (the
    select's transpose) backward. A grouped product writes its groups'
    rows and leaves the others as it found them, in its result and in
    the gradient it hands back alike: on the chip that is whatever the
    buffer held, NaN included, and 0 x NaN is NaN. So such rows are
    selected out wherever they would meet a product or a sum."""
    return jnp.where(held_row, a, 0)


def _grouped_experts(rows, gate_row, held_row, sizes, w_gate, w_up, w_down,
                     activation):
    """``(activation(rows W_g) * (rows W_u)) W_d`` weighted by ``gate_row``,
    by grouped products over ``sizes`` rows for each held expert; the rows
    behind the groups come out zero."""
    with jax.named_scope(_names.SCOPE_MOE_EXPERTS):
        dt = rows.dtype
        rows = _own(held_row, rows)
        hidden = _own(
            held_row,
            activation(lax.ragged_dot(rows, w_gate.astype(dt), sizes))
            * lax.ragged_dot(rows, w_up.astype(dt), sizes))
        # the route's weight goes onto the narrow side of the down
        # projection (w (h W_d) = (w h) W_d): f columns a row, not d
        hidden = _own(held_row, hidden * gate_row.astype(dt))
        return _own(
            held_row, lax.ragged_dot(hidden, w_down.astype(dt), sizes))


def _group_sizes(slot, n):
    return jnp.sum(
        slot[:, None] == jnp.arange(n, dtype=jnp.int32)[None, :],
        axis=0, dtype=jnp.int32)  # [held] rows of each group


def _all_rows(activation, x, weight, slot, order, w_gate, w_up, w_down):
    """The layer over all ``R = T * top_k`` sorted rows, the worst case
    (every route landing here): ``(y, sizes)``. The one execution where
    half the experts or more are held; elsewhere the branch a step takes
    when more routes arrive than the compact tier holds, and what the
    compact execution is tested against."""
    (T, d), (_, k), n = x.shape, weight.shape, w_gate.shape[0]
    R = T * k
    with jax.named_scope(_names.SCOPE_MOE_ROUTE):
        inverse = jnp.argsort(order).astype(jnp.int32)
        sizes = _group_sizes(slot, n)
        # sorted as the rows are: which of them belong to a group at all,
        # and each row's route's weight
        held_row = _permute_rows(
            (slot < n).reshape(R, 1), order, inverse)
        gate_row = _permute_rows(weight.reshape(R, 1), order, inverse)
        rows = _permute_rows(jnp.repeat(x, k, axis=0), order, inverse)
    rows = _grouped_experts(
        rows, gate_row, held_row, sizes, w_gate, w_up, w_down, activation)
    with jax.named_scope(_names.SCOPE_MOE_COMBINE):
        routes = _permute_rows(rows, inverse, order).reshape(T, k, d)
        y = jnp.sum(routes, axis=1, dtype=jnp.float32).astype(x.dtype)
    return y, sizes


def _sum_by_token(rows, token, T, k):
    """``[T, d]``: each of ``rows`` ``[C, d]`` added into row ``token[i]``,
    in float32, where no token has more than ``k`` rows. By gathers: on
    the chip XLA's scatter-add of the cell's 24,576 rows of 2,560 takes
    8.4 ms, sorted beforehand or not, and this 4.7 (``PERF.md``, PR 29).
    The rows are put in their tokens' order, so that a token's lie
    together; each token's first row takes in the up to ``k - 1`` behind
    it; each token reads its first row, if it has one."""
    C = rows.shape[0]
    by_token = jnp.argsort(token).astype(jnp.int32)
    tok, rows = token[by_token], rows[by_token]
    at = jnp.arange(C, dtype=jnp.int32)
    is_first = jnp.pad(tok[1:] != tok[:-1], (1, 0), constant_values=True)
    # how far behind its token's first row each row lies: under k
    behind = at - lax.cummax(jnp.where(is_first, at, 0))
    total = rows.astype(jnp.float32)
    for j in range(1, min(k, C)):
        follows = jnp.pad(behind[j:] == j, (0, j))[:, None]
        total += jnp.where(
            follows, jnp.pad(rows[j:], ((0, j), (0, 0))), 0
        ).astype(jnp.float32)
    # where each token's first row is: C integers scattered, not C rows
    first = jnp.full((T,), C, jnp.int32).at[jnp.where(is_first, tok, T)].set(
        at, mode="drop", unique_indices=True)
    return jnp.where(
        (first < C)[:, None], total[jnp.minimum(first, C - 1)], 0
    ).astype(rows.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _take_rows(x, token, T, k):
    """``x[token]`` (``T = len(x)``); backward is ``_add_rows``."""
    return x[token]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _add_rows(rows, token, T, k):
    """``_sum_by_token``; backward is ``_take_rows``: each is the other's
    transpose, and neither way is a scatter of rows (jax's own transpose
    of a gather is)."""
    return _sum_by_token(rows, token, T, k)


_take_rows.defvjp(
    lambda x, token, T, k: (x[token], token),
    lambda T, k, token, g: (_add_rows(g, token, T, k), None))
_add_rows.defvjp(
    lambda rows, token, T, k: (_sum_by_token(rows, token, T, k), token),
    lambda T, k, token, g: (_take_rows(g, token, T, k), None))


def _first_rows(C, activation, x, weight, order, sizes, w_gate, w_up,
                w_down):
    """The same layer over the first ``C`` sorted rows alone, for a step
    whose held routes all lie among them (``sum(sizes) <= C``: the stable
    sort puts them first). No array of ``R`` rows of ``d`` or ``f``
    columns exists, forward or backward: the rows are gathered from their
    tokens, and the weighted results added into their tokens in float32
    (the one's transpose is the other)."""
    (T, d), k = x.shape, weight.shape[1]
    with jax.named_scope(_names.SCOPE_MOE_ROUTE):
        first = order[:C]
        token = first // k
        held_row = (jnp.arange(C, dtype=jnp.int32) < jnp.sum(sizes))[:, None]
        gate_row = weight.reshape(T * k)[first][:, None]
        rows = _take_rows(x, token, T, k)
    rows = _grouped_experts(
        rows, gate_row, held_row, sizes, w_gate, w_up, w_down, activation)
    with jax.named_scope(_names.SCOPE_MOE_COMBINE):
        return _add_rows(rows, token, T, k)


def _tiers(C, activation, slot, order, sizes):
    """(whether the compact tier holds this step's held routes, the
    compact execution, the one over all rows): the two as functions of the
    layer's differentiable inputs ``x, weight, w_gate, w_up, w_down``."""

    def compact(x, weight, *w):
        return _first_rows(C, activation, x, weight, order, sizes, *w)

    def all_rows(x, weight, *w):
        return _all_rows(activation, x, weight, slot, order, *w)[0]

    return jnp.sum(sizes) <= C, compact, all_rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _tiered(C, activation, slot, order, sizes, *inputs):
    """The layer's result ``y`` by the execution the count of held routes
    selects; ``inputs``: ``x, weight, w_gate, w_up, w_down``.

    The derivative is the taken branch's alone. Differentiated as it
    stands, a ``lax.cond`` hands back every branch's residuals from every
    branch, zeros where they are not its own: the compact branch would
    write the worst case's ``[R, d]`` and ``[R, f]`` arrays after all. So
    forward saves the inputs, and backward makes the same choice again and
    pulls back through the one branch (whose forward it runs once more; a
    block recomputed in backward then computes this layer's result once,
    not twice: the recomputed one feeds nothing)."""
    return lax.cond(*_tiers(C, activation, slot, order, sizes), *inputs)


def _tiered_fwd(C, activation, slot, order, sizes, *inputs):
    return (lax.cond(*_tiers(C, activation, slot, order, sizes), *inputs),
            (slot, order, sizes, inputs))


def _tiered_bwd(C, activation, saved, g):
    slot, order, sizes, inputs = saved
    fits, *tiers = _tiers(C, activation, slot, order, sizes)
    pulls = [lambda g, *a, f=f: jax.vjp(f, *a)[1](g) for f in tiers]
    return (None, None, None) + lax.cond(fits, *pulls, g, *inputs)


_tiered.defvjp(_tiered_fwd, _tiered_bwd)


def softmax_route_weights(router_logits, top_k: int):
    """A token's ``top_k`` largest logits and the softmax over them (a
    softmax over all ``E`` renormalised over the chosen is the same
    numbers): ``(weight [T, top_k], chosen [T, top_k])``."""
    top, chosen = lax.top_k(router_logits, top_k)
    return jax.nn.softmax(top, axis=-1), chosen


def sigmoid_route_weights(scale: float = 1.0):
    """The rule of a router that scores each expert by itself: ``s =
    sigmoid(logit)``, a token's ``top_k`` largest ``s`` (the sigmoid is
    monotone: the largest logits, but ranked as the scores round), each
    over the chosen ones' sum, times ``scale`` (DeepSeek-V3's
    ``norm_topk_prob`` with a ``routed_scaling_factor``): the weights of a
    token sum to ``scale``.

    The divisor is the BARE sum, and this rule and the next are not one
    rule with an option. Published routers differ here: softmax routers
    that renormalise over the chosen (``softmax_route_weights``: the
    ``qwen3_next`` and ``smallthinker`` families) add nothing; DeepSeek-V3's
    published gate adds 1e-20 to the sum, which no float32 sum of sigmoids
    can see, so ``laguna-s-2-1``'s file (``assumed.router``) takes the bare
    sum of this function; the ``lfm2_moe`` family's adds 1e-6, which a sum
    of four scores in (0, 1) does see (a weight's sixth digit, and the
    benchmark's comparison with a plain reference reads it), and it also
    chooses by one number and weighs by another
    (:func:`biased_sigmoid_route_weights`). A configuration's file states
    which its source has; program and reference take that one."""

    def rule(router_logits, top_k: int):
        top, chosen = lax.top_k(jax.nn.sigmoid(router_logits), top_k)
        return scale * top / jnp.sum(top, axis=-1, keepdims=True), chosen

    return rule


def biased_sigmoid_route_weights(bias, scale: float = 1.0,
                                 eps: float = 1e-6):
    """The rule of a router that CHOOSES by one number and WEIGHS by
    another (DeepSeek-V3's auxiliary-loss-free balancing, arXiv:2408.15664;
    the ``lfm2_moe`` family's ``use_expert_bias``): ``s = sigmoid(logit)``;
    a token's experts are the ``top_k`` largest of ``s + bias``; their
    weights are the bare ``s`` over the chosen ones' sum plus ``eps``, times
    ``scale``. ``bias`` ``[E]`` float32 is a buffer, not a parameter: no
    gradient reaches it (it moves which experts are chosen, and a choice has
    no derivative); a rule outside the gradient moves it once a step from
    the counts this rule hands back (``models/decoder.py``
    ``make_moe_lm_loss_fn``).

    A rule of three results: ``(weight, chosen, (counts, turned))`` with
    ``counts`` ``[E]`` float32 the tokens that chose each of ALL the experts
    and ``turned`` the routes whose expert is not among the token's
    ``top_k`` largest bare scores (what the bias turned);
    :func:`moe_local_experts` hands the third back beside ``load`` and
    ``rows``."""
    bias = lax.stop_gradient(bias.astype(jnp.float32))

    def rule(router_logits, top_k: int):
        s = jax.nn.sigmoid(router_logits)
        _, chosen = lax.top_k(s + bias, top_k)
        top = jnp.take_along_axis(s, chosen, axis=-1)
        weight = scale * top / (jnp.sum(top, axis=-1, keepdims=True) + eps)
        # a chosen expert under the token's top_k-th largest bare score is
        # one the bare scores would not have chosen
        edge = lax.top_k(s, top_k)[0][:, -1:]
        counts = jnp.sum(
            chosen[:, :, None] == jnp.arange(s.shape[-1])[None, None, :],
            axis=(0, 1), dtype=jnp.float32)
        turned = jnp.sum(top < edge, dtype=jnp.float32)
        return weight, chosen, lax.stop_gradient((counts, turned))

    return rule


def moe_local_experts(
    x,
    router_logits,
    top_k: int,
    w_gate,
    w_up,
    w_down,
    held: Sequence[int],
    activation: Callable = jax.nn.relu,
    route_weights: Callable = softmax_route_weights,
):
    """This device's experts' part of a gated-feed-forward expert layer,
    with no route dropped.

    Parameters
    ----------
    x : ``[T, d]`` the tokens (any float dtype; the products run in it).
    router_logits : ``[T, E]`` float32 scores over ALL ``E`` experts.
    top_k : experts a token, chosen and weighted by ``route_weights``.
    w_gate, w_up : ``[held, d, f]``; w_down : ``[held, f, d]``: the stacked
        parameters of the experts held here, expert ``held[i]`` at ``i``.
    held : the ids, among the ``E``, of the experts held here (static).
    activation : the gate's (ReLU: ReGLU).
    route_weights : the router's rule, ``(logits [T, E] float32, top_k) ->
        (weight, chosen)``, both ``[T, top_k]``: which experts a token
        takes and what each one's result counts for. The default,
        :func:`softmax_route_weights`, is the softmax over the token's
        ``top_k`` largest logits; :func:`sigmoid_route_weights` scores by
        sigmoid, normalises over the chosen and scales;
        :func:`biased_sigmoid_route_weights` chooses by the scores plus a
        bias and weighs by the bare scores. Everything after it (ordering,
        the tiers, the products, the sum) is the same. A rule may hand back
        a third result, what it measured of its choice: it comes back
        after ``rows``.

    Every route to a held expert is kept: the routes are ordered by
    expert (a stable sort: the held experts' groups first, the routes to
    experts held elsewhere behind every group), the three products of
    ``activation(x W_g) * (x W_u)) W_d`` run as grouped products over the
    held experts' row groups (``lax.ragged_dot``), and the weighted rows
    are added back to their tokens. Rows of no group count for nothing
    (they are set to zero on either side of every grouped product).

    Shapes are static, in two tiers. The worst case is all ``R = T *
    top_k`` routes landing here; the expected case is ``held / E`` of
    them. Where fewer than half the experts are held, a step whose held
    routes fit ``compact_rows(R, held, E)`` rows (twice the expected
    share) does all its row work (gathering, masking, the products, the
    sum) over that many rows, and a step with more takes the worst case's
    execution: one ``lax.cond`` on the count, both branches the same
    mathematics, nothing dropped in either. Where half or more are held
    there is the worst case's execution alone. Bookkeeping is int32.

    Returns ``(y [T, d], load [held] float32, rows [] float32)``: the
    tokens each held expert received, and the rows this call's grouped
    products ran over (``R`` or the compact tier's); then, under a rule of
    three results, the rule's third.
    """
    T, d = x.shape
    E = router_logits.shape[-1]
    k, n = int(top_k), len(held)
    if router_logits.shape != (T, E) or not 1 <= k <= E:
        raise ValueError(
            f"router_logits must be [T={T}, E>={k}], got "
            f"{tuple(router_logits.shape)} with top_k={k}")
    if len(set(held)) != n or not all(0 <= e < E for e in held):
        raise ValueError(f"held must be distinct ids under {E}, got {held}")
    if not (w_gate.shape == w_up.shape == (n, d, w_gate.shape[-1])
            and w_down.shape == (n, w_gate.shape[-1], d)):
        raise ValueError(
            f"expected [{n}, {d}, f], [{n}, {d}, f], [{n}, f, {d}]; got "
            f"{w_gate.shape}, {w_up.shape}, {w_down.shape}")
    R = T * k
    C = compact_rows(R, n, E)
    with jax.named_scope(_names.SCOPE_MOE_ROUTE):
        # [T, k] float32 weights, [T, k] expert ids
        weight, chosen, *noted = route_weights(
            router_logits.astype(jnp.float32), k)
        # slot of each route: its expert's place among the held, or n
        slot_of = np.full((E,), n, np.int32)
        slot_of[list(held)] = np.arange(n, dtype=np.int32)
        slot = jnp.asarray(slot_of)[chosen].reshape(R)
        order = jnp.argsort(slot, stable=True).astype(jnp.int32)
    if C == R:
        y, sizes = _all_rows(
            activation, x, weight, slot, order, w_gate, w_up, w_down)
        return (y, sizes.astype(jnp.float32), jnp.float32(R), *noted)
    with jax.named_scope(_names.SCOPE_MOE_ROUTE):
        sizes = _group_sizes(slot, n)
    y = _tiered(
        C, activation, slot, order, sizes, x, weight, w_gate, w_up, w_down)
    rows = jnp.where(jnp.sum(sizes) <= C, C, R).astype(jnp.float32)
    return (y, sizes.astype(jnp.float32), rows, *noted)


def note_expert_layers(tokens: int, top_k: int, layers: int,
                       held: int) -> None:
    """Publish what one step's expert layers route, from static shapes:
    called by a model while its forward pass is traced, so the gauges
    describe the step most recently traced (as ``nn._note_sync``'s do).
    ``tokens`` are this rank's. The rows of the grouped products are set
    to the worst case here, every route, so that a step whose state was
    never read still has a value; ``note_expert_load`` sets them to what
    a step's layers took."""
    m = _telemetry.metrics
    routes = int(tokens) * int(top_k) * int(layers)
    m.gauge(
        "tm_moe_routes_per_step",
        "routes each rank makes per step: tokens x top_k x expert layers "
        "(static shapes of the step most recently traced)",
    ).set(routes)
    _grouped_rows_gauge().set(routes)
    m.gauge(
        "tm_moe_experts_held",
        "experts this rank holds in each expert layer",
    ).set(int(held))


def _grouped_rows_gauge():
    return _telemetry.metrics.gauge(
        "tm_moe_grouped_rows_per_step",
        "rows each rank's grouped expert products ran over, summed over "
        "the layers, in the last step read: a layer's compact tier or "
        "all its routes (all of them until a step is read)",
    )


def note_expert_load(load, rows) -> None:
    """Publish a step's measured routing (host arrays). ``load``
    ``[layers, held]``, the tokens each held expert received: the routes
    kept here, and the worst layer's largest load over its mean load.
    ``rows`` ``[layers]``, the rows each layer's grouped products ran
    over: their sum, and the layers that took the compact tier (fewer
    rows than the layer's routes, as ``note_expert_layers`` published
    them when the step was traced)."""
    load = np.asarray(load, np.float64)
    rows = np.asarray(rows, np.float64)
    m = _telemetry.metrics
    m.gauge(
        "tm_moe_held_routes_last_step",
        "routes to experts held on this rank in the last step read, "
        "summed over layers",
    ).set(float(load.sum()))
    mean = load.mean(axis=-1)
    m.gauge(
        "tm_moe_max_over_mean_load",
        "largest held expert's tokens over the mean held expert's, of "
        "the layer where that is worst, in the last step read",
    ).set(float(np.max(load.max(axis=-1) / np.maximum(mean, 1e-9))))
    _grouped_rows_gauge().set(float(rows.sum()))
    routes = m.gauge("tm_moe_routes_per_step").value() or 0.0
    m.gauge(
        "tm_moe_compact_layers_last_step",
        "expert layers whose held routes fit the compact tier of rows in "
        "the last step read, so that no row work was sized for all routes",
    ).set(float(np.sum(rows < routes / len(rows))))


def note_expert_bias(bias, turned) -> None:
    """Publish, of a step read (host arrays), what a router that chooses by
    its scores plus a bias (:func:`biased_sigmoid_route_weights`) carries:
    the largest magnitude among the biases ``[layers, E]`` as the step left
    them, and the routes ``[layers]`` whose expert the bare scores would not
    have chosen, summed over the layers."""
    m = _telemetry.metrics
    m.gauge(
        _names.GAUGE_MOE_BIAS_MAX_ABS,
        "largest magnitude among the routers' expert biases, over every "
        "expert layer, as the last step read left them",
    ).set(float(np.max(np.abs(np.asarray(bias, np.float64)))))
    m.gauge(
        _names.GAUGE_MOE_BIASED_ROUTES,
        "routes of the last step read whose expert is not among the "
        "token's top_k largest bare scores (what the bias turned), summed "
        "over the layers",
    ).set(float(np.sum(np.asarray(turned, np.float64))))
