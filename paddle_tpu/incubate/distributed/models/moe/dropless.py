"""Dropless expert layer: no capacity, no dropped token, and an expert-
parallel rank's share of the result.

`moe_layer.py` beside this file routes through a one-hot ``[T, E, C]``
dispatch mask with a capacity that drops tokens; at 8,192 tokens and 128
experts that mask is not a program a chip can hold.  Here the (token, expert)
pairs are SORTED by expert and the experts run as one grouped matrix product
over uneven group sizes:

  route   scores over ALL experts (the router keeps its published width),
          top-k with a selection bias, weights normalised and scaled;
  share   a rank is told which experts it holds (``offset``, and the leading
          dim of its expert-stacked weights) and computes the part of the
          result its own experts give: the pairs whose expert lives
          elsewhere are sorted past the held groups and contribute nothing.
          On one chip there is no exchange, and nothing stands in for one;
  group   rows per held expert are COUNTED; the row buffer has the static
          bound T * k (every pair could be held), the grouped products do
          the work of the counted rows only, and the other passes over the
          rows run at twice the rows the share expects, or at T * k when the
          count passes that (chosen on the device);
  combine the pairs' rows go back by the inverse permutation (a gather and a
          sum over k, in both directions — never a scatter-add).

The grouped product has two implementations, chosen by the static shapes
alone.  ``jax.lax.ragged_dot``, which XLA lowers on the TPU to its own Mosaic
grouped-matmul kernel (the instruction is named ``%ragged-dot-*`` and carries
``ragged_dot_tiling=`` among its frontend attributes, which a device trace
keeps), forward and both gradients, with a tiling of XLA's choosing: 512 x
512 x 512 for a train step's ~1,000 rows an expert (`grouped_swiglu` as the
train step calls it), which is right there, but (tm, tk, tn) = 64 x 512 x 128
and 512 x 512 x 128 for a serving batch's 704 and 11,264 rows over 128
experts — 128 KB weight tiles, ~5,000 grid steps a call.  Where a group has
few rows and nothing is differentiated (`grouped_relu2`, and `grouped_swiglu`
as a serving path calls it, on the chip) the product is
`ops/pallas/grouped_matmul.py`, the same megablox algorithm with whole-K,
wide-N weight tiles and a row tile that follows the rows a group has.  Both
carry `TRACE_LABEL` in their instruction's text, so a trace finds either.

What is NOT a grouped product is plain ``jax.numpy`` that XLA fuses, and a
trace finds it by the region it was traced under (`profiler.device_span`):
``moe.route``, ``moe.dispatch`` (the sort and the row gathers),
``moe.experts`` (the products and what lies between them), ``moe.combine``,
``moe.balance`` — one name each for training and serving, because the
functions are one.  A model labels the rest of its expert layer
``moe.layer`` / ``moe.shared``; the backward of each carries its name.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .....ops.pallas.grouped_matmul import (TRACE_LABEL, grouped_matmul,
                                            tiles as _kernel_tiles)
from .....profiler import device_span

__all__ = ["sigmoid_topk_route", "sort_pairs_by_held_expert",
           "grouped_swiglu", "grouped_relu2", "dropless_expert_ffn",
           "dropless_expert_forward", "expert_load", "balance_bias_update",
           "row_bounds", "row_tier", "TRACE_LABEL"]


@device_span("moe.route")
def sigmoid_topk_route(u, w_router, bias, top_k, route_scale=1.0,
                       route_norm=True, precision=None):
    """u [T, H], w_router [H, E_total], bias [E_total] -> (sel int32 [T, k],
    weights f32 [T, k]).  Scores are sigmoids in float32; the BIAS takes part
    in the selection only, the weights are the unbiased scores of the
    selected experts, normalised over the k (``route_norm``) and scaled.
    ``precision`` is the score product's (None: the backend's default)."""
    scores = jax.nn.sigmoid(jax.lax.dot_general(
        u, w_router.astype(u.dtype), (((1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32))
    _, sel = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    # the selected scores by a one-hot select-and-sum: its transpose is a
    # broadcast, where take_along_axis's would be a scatter-add
    chosen = sel[..., None] == jnp.arange(scores.shape[-1])[None, None, :]
    w = jnp.where(chosen, scores[:, None, :], 0.0).sum(-1)
    if route_norm:     # a python bool  # graftlint: disable=TRACE001
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), w * route_scale


def expert_load(sel, num_experts):
    """sel [T, k] -> int32 [num_experts]: the tokens each of ALL the experts
    was selected by (counted by comparison, not by a scatter-add)."""
    return (sel[..., None] == jnp.arange(num_experts, dtype=sel.dtype)) \
        .sum((0, 1), dtype=jnp.int32)


@device_span("moe.balance")
def balance_bias_update(bias, load, coeff):
    """The selection bias after a step, by the auxiliary-loss-free rule: an
    expert under the mean load gains ``coeff``, one over it loses ``coeff``,
    and the deltas are centred.  bias f32 [..., E], load [..., E] (the
    experts on the last dim) -> f32 [..., E]."""
    load = load.astype(jnp.float32)
    delta = coeff * jnp.sign(load.mean(-1, keepdims=True) - load)
    return bias + (delta - delta.mean(-1, keepdims=True))


@device_span("moe.dispatch")
def sort_pairs_by_held_expert(sel, offset, held):
    """sel [T, k] (ids over all experts) -> (order, inverse, held_mask,
    rows).  ``order`` [T*k] lists the flat pairs sorted by held-expert id,
    the pairs whose expert is not in [offset, offset + held) last;
    ``inverse`` is its inverse permutation; ``held_mask`` bool [T, k];
    ``rows`` int32 [held] counts the rows of each held expert — the group
    sizes of the grouped product, and the layer's counter."""
    local = sel - offset
    held_mask = (local >= 0) & (local < held)
    key = jnp.where(held_mask, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    # counted by comparison, not by a scatter-add of T*k ones
    rows = (key[:, None] == jnp.arange(held, dtype=key.dtype)[None, :]) \
        .sum(0, dtype=jnp.int32)
    return order, inverse, held_mask, rows


# The two movements between tokens and sorted rows.  ``order`` is cut to the
# row bound B (the first B sorted pairs: every held pair, as long as their
# count is <= B), ``inverse`` says where each of the T*k pairs went.  A
# token's sum over its own k pairs is a gather and a reduction in BOTH
# directions, where autodiff of a gather would scatter-add.
def _rows_of_tokens(x, order, k):
    """[T, H] -> [B, H]: the token's row for each of the first B sorted
    pairs."""
    return x[order // k]


def _sum_over_pairs(rows, inverse, held_mask, weights=None):
    """[B, H] -> [T, H] float32: each token's sum over its HELD pairs of
    their (weighted) rows.  A pair whose expert lives elsewhere names a row
    of no group (or none at all, past the bound): selected away, never read
    into the sum — whatever a grouped product left there."""
    per_pair = rows[jnp.minimum(inverse, rows.shape[0] - 1)].reshape(
        held_mask.shape + rows.shape[-1:]).astype(jnp.float32)
    if weights is not None:
        per_pair = per_pair * weights[..., None]
    return jnp.where(held_mask[..., None], per_pair, 0.0).sum(1)


@jax.custom_vjp
def _dispatch(u, order, inverse, held_mask):
    return _rows_of_tokens(u, order, held_mask.shape[1])


_dispatch.defvjp(
    lambda u, order, inverse, held_mask: (
        _rows_of_tokens(u, order, held_mask.shape[1]), (inverse, held_mask)),
    lambda res, g: (_sum_over_pairs(g, *res).astype(g.dtype),
                    None, None, None))


@jax.custom_vjp
def _combine(ys, weights, order, inverse, held_mask):
    """out[t] = sum over t's held pairs of weight * row, float32 weights."""
    return _sum_over_pairs(ys, inverse, held_mask, weights).astype(ys.dtype)


def _combine_bwd(res, g):
    ys, weights, order, inverse, held_mask = res
    g_rows = _rows_of_tokens(g, order, held_mask.shape[1]) \
        .astype(jnp.float32)
    w_row = weights.reshape(-1)[order]
    d_w_row = (g_rows * ys.astype(jnp.float32)).sum(-1)
    d_w = jnp.where(held_mask, d_w_row[jnp.minimum(
        inverse, ys.shape[0] - 1)].reshape(held_mask.shape), 0.0)
    return (g_rows * w_row[:, None]).astype(ys.dtype), d_w, None, None, None


_combine.defvjp(
    lambda ys, weights, order, inverse, held_mask: (
        _combine(ys, weights, order, inverse, held_mask),
        (ys, weights, order, inverse, held_mask)), _combine_bwd)


def _grouped_product(xs, w, rows, kernel, **kernel_kw):
    """``ragged_dot``, or the Pallas kernel where ``kernel`` allows it and
    the static shapes say a group has few rows (`grouped_matmul.tiles`)."""
    chosen = kernel and _kernel_tiles(*xs.shape, w.shape[2], w.shape[0],
                                      xs.dtype.itemsize)
    if chosen:
        return grouped_matmul(xs, w, rows, tm=chosen[0], tn=chosen[1],
                              **kernel_kw)
    return jax.lax.ragged_dot(xs, w.astype(xs.dtype), rows)


def grouped_swiglu(xs, we_gate, we_up, we_down, rows, *, kernel=False,
                   role=None, interpret=False):
    """Rows sorted by expert, ``rows[e]`` of them for expert e ->
    (silu(xs W_gate[e]) * (xs W_up[e])) W_down[e] per group.  Rows past
    sum(rows) belong to no group; what they hold afterwards is unspecified.

    As called by the train step (``kernel`` left False) the three products
    are ``ragged_dot``, forward and both gradients.  A serving path that
    never differentiates passes its platform test as ``kernel``, and each
    product then takes `grouped_matmul` where the static shapes say a group
    has few rows, under :func:`grouped_relu2`'s rule."""
    kw = dict(role=role, interpret=interpret)
    with device_span("moe.experts"):
        gate = _grouped_product(xs, we_gate, rows, kernel, **kw)
        up = _grouped_product(xs, we_up, rows, kernel, **kw)
        return _grouped_product(jax.nn.silu(gate) * up, we_down, rows,
                                kernel, **kw)


def grouped_relu2(xs, we_up, we_down, rows, *, kernel=False, role=None,
                  interpret=False):
    """The two-matrix expert ``relu(xs W_up[e])^2 W_down[e]`` per group, as
    :func:`grouped_swiglu` is the three-matrix one; forward only.

    ``kernel`` says the Pallas kernel MAY run (the caller's platform test:
    a TPU, or ``interpret``); whether it does is read off the shapes, each
    product for itself: ``xs.shape[0] // rows.shape[0]`` rows a group at
    most 256 and lane-aligned widths take `grouped_matmul` (a decode batch's
    704 / 128 = 5: row tile 64; a 1,024-token chunk's 88 or 176: row tile
    128; an expert's whole matrix a weight tile where XLA takes 512 x 128),
    anything else ``ragged_dot`` (XLA's 512-cubed tiles are right for the
    1,024 rows an expert of a train step).  ``role`` labels the kernel's
    calls in a trace ("decode" | "prefill")."""
    kw = dict(role=role, interpret=interpret)
    with device_span("moe.experts"):
        up = _grouped_product(xs, we_up, rows, kernel, **kw)
        return _grouped_product(jnp.square(jax.nn.relu(up)), we_down, rows,
                                kernel, **kw)


def _experts_within(bound, operands, sorting, expert=grouped_swiglu):
    """The held experts' part with every pass over the rows cut to the
    static ``bound`` (exact while the counted rows are <= bound).
    ``operands`` are (u, weights, the expert's matrices...), ``expert`` the
    grouped product they feed."""
    u, weights, *matrices = operands
    order, inverse, held_mask, rows = sorting
    with device_span("moe.dispatch"):
        first = order[:bound]
        xs = _dispatch(u, first, inverse, held_mask)
    ys = expert(xs, *matrices, rows)
    with device_span("moe.combine"):
        return _combine(ys, weights, first, inverse, held_mask)


def row_tier(bounds, rows):
    """Index of the smallest of the ascending ``bounds`` that holds the
    counted rows (the last one holds any count)."""
    return sum((rows.sum() > b).astype(jnp.int32) for b in bounds[:-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _experts_tiered(bounds, operands, sorting):
    """`_experts_within` the smallest of the static ``bounds`` that holds
    the counted rows, chosen on the device.  Forward and backward each take
    their own ``lax.switch`` and the backward recomputes the rows it needs
    inside its branch: autodiff THROUGH a switch would keep every branch's
    [bound, .] residuals alive (zeros for the branches not taken), the
    largest bound's among them, in every layer."""
    return jax.lax.switch(
        row_tier(bounds, sorting[3]),
        [functools.partial(_experts_within, b) for b in bounds],
        operands, sorting)


def _experts_tiered_bwd(bounds, res, g):
    operands, sorting = res

    def backward(bound, operands, sorting, g):
        return jax.vjp(lambda *ops: _experts_within(bound, ops, sorting),
                       *operands)[1](g)

    return jax.lax.switch(
        row_tier(bounds, sorting[3]),
        [functools.partial(backward, b) for b in bounds],
        operands, sorting, g), None


_experts_tiered.defvjp(
    lambda bounds, operands, sorting: (
        _experts_tiered(bounds, operands, sorting), (operands, sorting)),
    _experts_tiered_bwd)


def row_bounds(t, k, held, num_experts):
    """The two static row bounds of a share: twice the rows it expects, and
    every pair."""
    expected_twice = max(2 * t * k * held // num_experts, 1)
    return tuple(sorted({min(expected_twice, t * k), t * k}))


def dropless_expert_forward(u, sel, weights, matrices, offset, num_experts,
                            expert=grouped_relu2):
    """The forward pass alone of :func:`dropless_expert_ffn`, for a path
    that never differentiates (serving): the same route / sort / grouped
    product / combine over the same two row bounds, the tier chosen on the
    device by a plain ``lax.switch``, for any ``expert`` (``matrices`` are
    its expert-stacked weights, the HELD experts on their leading dim).
    Returns (out [T, H'] in u's dtype, rows int32 [held], beyond int32:
    the held pairs whose sorted row lies past the chosen tier's bound —
    the rows a tier would DROP (their tokens would combine another pair's
    row); 0 while the largest bound is every pair)."""
    t, k = sel.shape
    sorting = sort_pairs_by_held_expert(sel, offset, matrices[0].shape[0])
    bounds = row_bounds(t, k, matrices[0].shape[0], num_experts)
    tier = row_tier(bounds, sorting[3])
    # the `conditional` encloses the products: it takes THEIR name, so that
    # a trace's glue (`moe.layer` ...) is not the whole layer's interval
    with device_span("moe.experts"):
        out = jax.lax.switch(
            tier,
            [functools.partial(_experts_within, b, expert=expert)
             for b in bounds],
            (u, weights, *matrices), sorting)
    beyond = jnp.maximum(
        sorting[3].sum() - jnp.asarray(bounds, jnp.int32)[tier], 0)
    return out, sorting[3], beyond


def dropless_expert_ffn(u, sel, weights, we_gate, we_up, we_down, offset,
                        num_experts):
    """The held experts' part of sum_{e in sel} w_e Expert_e(u).

    u [T, H]; sel / weights [T, k] from `sigmoid_topk_route` over all
    ``num_experts``; we_* carry the HELD experts on their leading dim, global
    ids offset .. offset + held.  Returns (out [T, H] in u's dtype, rows
    int32 [held]).

    Exact for any routing: the row buffer's bound is T*k.  The WORK follows
    the counted rows: the grouped products by their group sizes, and every
    other pass over the rows by a static bound of TWICE the rows the share
    expects (T*k*held/num_experts), or by T*k when the count passes that,
    chosen on the device.  Nothing of a layer's [bound, .] buffers is kept
    for the backward pass, which recomputes them."""
    t, k = sel.shape
    held = we_gate.shape[0]
    sorting = sort_pairs_by_held_expert(sel, offset, held)
    bounds = row_bounds(t, k, held, num_experts)
    # the forward's and the backward's `conditional` take the products'
    # name, as in `dropless_expert_forward`
    with device_span("moe.experts"):
        out = _experts_tiered(
            bounds, (u, weights, we_gate, we_up, we_down), sorting)
    return out, sorting[3]
