"""Grouped matrix products for routed experts: every expert held here applied
to the token slots routed to it, nothing dropped, static shapes.

A routed layer sends each of ``T`` tokens to ``k`` experts: ``T * k``
*slots*. Of the experts a chip holds only some; :func:`sort_slots` orders the
slots so that those of the first held expert come first, then the second's,
…, and the slots of experts held elsewhere last. The sorted buffer always has
``T * k`` rows — the bound when every token picks held experts only — but
the grouped product (:func:`grouped_matmul`) visits only the row tiles its
groups cover, so its time follows the slots that came, not the bound.

The product is ``jax.lax.ragged_dot``: on a TPU, XLA lowers it to its own
Mosaic kernels (a metadata pass over the group sizes and a tiled product
whose grid ends at the last active tile), differentiable in both operands
without a custom VJP, and plain masked matmuls on the CPU. The Pallas
``megablox.gmm`` computes the same product by the same scheme and was read
beside it on the chip at the benchmark's shapes
(``benchmarks/tests/grouped_product_on_chip.py``; PERF.md §6, PR 26): it ran
a layer's three products 3% faster forward and 14% faster forward and
backward, which is 0.4% of the step they sit in, and needs a VJP of its own
and the Pallas interpreter off the chip. One code path everywhere was kept.
Both leave whatever was in memory in the rows past the last group, so
:func:`grouped_matmul` fences them on both sides.
"""

import functools

import jax
import jax.numpy as jnp


def sort_slots(expert_of_slot, first, held):
    """Order the slots by the expert held here that each goes to.

    ``expert_of_slot`` (``int32 [S]``) names each slot's expert among all
    the router's; this chip holds experts ``first … first + held - 1``.
    Returns ``(order, group_sizes)``: ``order`` (``int32 [S]``) lists the
    slots grouped by held expert, in expert order, the slots of experts not
    held at the end; ``group_sizes`` (``int32 [held]``) counts each held
    expert's slots."""
    local = expert_of_slot - first
    local = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    group_sizes = jnp.sum(
        local[:, None] == jnp.arange(held, dtype=local.dtype)[None, :], axis=0, dtype=jnp.int32)
    return order, group_sizes


def slot_places(order):
    """The inverse of :func:`sort_slots`' ``order``: where each slot sits in
    the sorted buffer."""
    return jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype), unique_indices=True, mode="promise_in_bounds")


def _rows(x, index):
    return x.at[index].get(mode="promise_in_bounds")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def rows_to_slots(rows, order, place, k):
    """``[T, d]`` token rows to the sorted slot buffer ``[T * k, d]``: sorted
    slot ``i`` is slot ``order[i]``, which belongs to token ``order[i] //
    k``. The gradient comes back by the inverse permutation (``place``) and a
    sum over each token's ``k`` slots — a gather, where the gather's own
    transpose would be a scatter-add over repeated rows."""
    return _rows(rows, order // k)


def _rows_to_slots_fwd(rows, order, place, k):
    return _rows(rows, order // k), (order, place)


def _rows_to_slots_bwd(k, res, d_sorted):
    _order, place = res
    per_slot = _rows(d_sorted, place)
    d_rows = jnp.sum(per_slot.reshape(-1, k, per_slot.shape[-1]), axis=1, dtype=jnp.float32)
    return d_rows.astype(d_sorted.dtype), None, None


rows_to_slots.defvjp(_rows_to_slots_fwd, _rows_to_slots_bwd)


@jax.custom_vjp
def slots_to_order(sorted_rows, order, place):
    """The sorted buffer back in slot order (``[T * k, d]``, slot ``t * k +
    j`` the ``j``-th choice of token ``t``); the gradient returns by
    ``order``, again a gather."""
    return _rows(sorted_rows, place)


def _slots_to_order_fwd(sorted_rows, order, place):
    return _rows(sorted_rows, place), (order,)


def _slots_to_order_bwd(res, d_rows):
    return _rows(d_rows, res[0]), None, None


slots_to_order.defvjp(_slots_to_order_fwd, _slots_to_order_bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs[rows of group g] @ rhs[g]`` for every group: ``lhs`` ``[S, K]``
    sorted by group, ``rhs`` ``[G, K, N]``, ``group_sizes`` ``int32 [G]``.
    Rows past the last group come back as zeros and pass no gradient (the
    kernels neither read nor write them, so both sides are fenced here)."""
    used = (jnp.arange(lhs.shape[0], dtype=jnp.int32) < jnp.sum(group_sizes))[:, None]
    out = jax.lax.ragged_dot(
        jnp.where(used, lhs, 0), rhs, group_sizes, preferred_element_type=lhs.dtype)
    return jnp.where(used, out, 0)
