"""Grouped matrix products for routed experts: every expert held here applied
to the token slots routed to it, nothing dropped, static shapes.

A routed layer sends each of ``T`` tokens to ``k`` experts: ``S = T * k``
*slots*. Of the experts a chip holds only some; :func:`sort_slots` orders the
slots so that those of the first held expert come first, then the second's,
…, and the slots of experts held elsewhere last. Only the head of that order
is work: the *slot buffer* is its first ``R`` rows (:func:`rows_to_slots`,
the products, :func:`slots_to_tokens`), and everything ``d`` or ``width``
wide between the sort and the sum over ``k`` is ``R`` rows long. ``R = S`` is
the bound (every token picked held experts only) and always right; a chip
that holds ``held`` of ``E`` experts gets about ``S * held / E`` slots, so
the layer runs on :func:`compact_rows` ``= C`` rows, twice the even share,
and on all ``S`` slots in any step whose held slots do not fit in ``C``
(:func:`either`: a ``lax.cond`` on the device's own count; the fallback takes
a share of the tokens at a time, so it too holds ``C`` rows at most, and
gives what one pass over ``S`` rows gives): no capacity, nothing dropped,
whatever the routing. What stays ``S`` long: the int32 /
float32 vectors (the sort's key ``local``, its ``order``, the weights), all
made by element-wise work, one sort and masked sums: the inverse of the order
(:func:`slot_places`, a scatter) is computed only where the buffer is the
whole ``S`` slots. The way back to token
order (:func:`slots_to_tokens` forward, :func:`rows_to_slots` backward)
reads the compact buffer's rows and nothing else: inside an expert's group
the rows stand in token order, so a tile of tokens finds its slots of one
expert in one range of rows, and one kernel
(:mod:`~tensorflowonspark_tpu.ops.moe_combine`) sums each token's held slots
window by window.

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

from tensorflowonspark_tpu.ops import moe_combine


def sort_slots(expert_of_slot, first, held):
    """Order the slots by the expert held here that each goes to.

    ``expert_of_slot`` (``int32 [S]``) names each slot's expert among all
    the router's; this chip holds experts ``first … first + held - 1``.
    Returns ``(order, group_sizes, local)``: ``order`` (``int32 [S]``) lists
    the slots grouped by held expert, in expert order, the slots of experts
    not held at the end; ``group_sizes`` (``int32 [held]``) counts each held
    expert's slots; ``local`` (``int32 [S]``, slot order) is the key it sorted
    by, each slot's expert among the held ones, ``held`` for an expert held
    elsewhere: all the fallback needs to sort a share of the slots again."""
    local = expert_of_slot - first
    local = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    group_sizes = jnp.sum(
        local[:, None] == jnp.arange(held, dtype=local.dtype)[None, :], axis=0, dtype=jnp.int32)
    return order, group_sizes, local


#: a compact slot buffer's length is a multiple of this many rows
ROW_TILE = 512
#: the device scope of sorting the slots (``models/decoder.py``'s, where the fallback sorts again)
SORT_SCOPE = "tos.moe_route"


def compact_rows(slots, held, experts):
    """The slot buffer's length ``C`` for a chip that holds ``held`` of the
    router's ``experts``: twice the even share of the ``slots``, a multiple
    of :data:`ROW_TILE`, at most ``slots`` (all of them where all the experts
    are held, or the share is a half or more)."""
    return min(slots, -(-2 * slots * held // (experts * ROW_TILE)) * ROW_TILE)


def slot_places(order):
    """The inverse of :func:`sort_slots`' ``order``: where each slot sits in
    the sorted buffer. A scatter, which a TPU walks index by index (0.61 ms
    a call at ``sdar-30b-a3b.bd4-packed4k``'s 131,072 slots: PERF.md §6, PR
    43), so only :func:`_sum_over_slots` on a whole buffer computes it."""
    return jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype), unique_indices=True, mode="promise_in_bounds")


def _rows(x, index):
    return x.at[index].get(mode="promise_in_bounds")


def _sum_over_slots(buffer, order, group_sizes, k, mesh):
    """``[T, d]`` in ``buffer``'s type: each token's ``k`` slots found in
    ``buffer`` (``[R, d]``: the first ``R`` of the sorted ``order``, ``int32
    [T * k]``) and summed in float32; a slot that is not in the buffer adds
    nothing. From the whole buffer (``R = T * k``: every slot is there) that
    is one gather back to slot order by :func:`slot_places` and a sum over
    ``[T, k, d]``, in slot order. From a shorter one it is
    :func:`moe_combine.combine
    <tensorflowonspark_tpu.ops.moe_combine.combine>`, a kernel (interpreted
    anywhere but on a TPU) that reads the buffer's rows a window at a time by
    ``order[:R]`` and ``group_sizes`` and sums a token's held slots in held-expert
    order: no zero row behind the buffer, nothing ``T * k`` rows long, no
    gather (``k`` gathers of ``[T, d]`` before PR 42, seven in eight of their
    rows the zero row where a chip holds an eighth of the experts; read on the
    chip against them, against one gather of ``[T * k, d]`` and against a
    scatter-add of the ``R`` rows: PERF.md §6, PR 35 and PR 42), and no
    inverse of the order.

    A Mosaic call has no partitioning rule, and JAX refuses to lower one on
    more than one chip outside a ``shard_map``: on a ``mesh`` of several
    devices the kernel runs under one. The sum is independent column by
    column and by nothing else (the order names tokens of the whole batch), so
    the columns are split over ``tp`` where every shard keeps whole lanes and
    every other operand is whole on every chip."""
    rows, slots = buffer.shape[0], order.shape[0]
    if rows == slots:
        per_slot = _rows(buffer, slot_places(order))
        return jnp.sum(per_slot.reshape(-1, k, per_slot.shape[-1]), axis=1, dtype=jnp.float32).astype(buffer.dtype)
    token_of_row = order[:rows] // k
    run = functools.partial(moe_combine.combine, tokens=slots // k, interpret=jax.default_backend() != "tpu")
    if mesh is None or mesh.size == 1:
        return run(buffer, token_of_row, group_sizes)
    from jax.sharding import PartitionSpec as P

    from tensorflowonspark_tpu.parallel.collectives import shard_map

    shards = dict(zip(mesh.axis_names, mesh.devices.shape)).get("tp", 1)
    columns = P(None, "tp" if shards > 1 and buffer.shape[1] % (128 * shards) == 0 else None)
    # check_vma off: pallas_call outputs carry no varying-axes type
    return shard_map(run, mesh=mesh, in_specs=(columns, P(), P()), out_specs=columns, check_vma=False)(
        buffer, token_of_row, group_sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def rows_to_slots(rows, order, group_sizes, length, k, mesh=None):
    """``[T, d]`` token rows to the head of the sorted slot buffer,
    ``[length, d]``: sorted slot ``i`` is slot ``order[i]``, which belongs to
    token ``order[i] // k``. The gradient is the sum over each token's slots
    that lie in the buffer (:func:`_sum_over_slots`, by ``order`` and the
    ``group_sizes``; ``mesh``: the devices the step runs on, if more than one)
    where the gather's own transpose would be a scatter-add over repeated
    rows."""
    return _rows(rows, order[:length] // k)


def _rows_to_slots_fwd(rows, order, group_sizes, length, k, mesh):
    return rows_to_slots(rows, order, group_sizes, length, k, mesh), (order, group_sizes)


def _rows_to_slots_bwd(length, k, mesh, res, d_sorted):
    return _sum_over_slots(d_sorted, *res, k, mesh), None, None


rows_to_slots.defvjp(_rows_to_slots_fwd, _rows_to_slots_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def slots_to_tokens(sorted_rows, order, group_sizes, k, mesh=None):
    """The head of the sorted buffer (``[R, d]``) summed into its tokens,
    ``[T, d]`` in the buffer's type (:func:`_sum_over_slots`). The gradient is
    ``R`` rows gathered from ``[T, d]`` by ``order[:R] // k`` (no ``[T, k,
    d]`` broadcast)."""
    return _sum_over_slots(sorted_rows, order, group_sizes, k, mesh)


def _slots_to_tokens_fwd(sorted_rows, order, group_sizes, k, mesh):
    return slots_to_tokens(sorted_rows, order, group_sizes, k, mesh), (order[:sorted_rows.shape[0]],)


def _slots_to_tokens_bwd(k, mesh, res, d_tokens):
    return _rows(d_tokens, res[0] // k), None, None


slots_to_tokens.defvjp(_slots_to_tokens_fwd, _slots_to_tokens_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def either(fn, compact, fits, per_token, shared, order, local, group_sizes):
    """``fn(compact, *per_token, *shared, order, group_sizes)``, the layer on
    the first ``compact`` rows of the sorted order, where ``fits`` (a traced
    bool: every held slot is among them), and else the layer on all the slots
    (:func:`_on_every_slot`): every held slot computed whatever the routing.
    One ``jax.lax.cond``, differentiable in ``per_token`` (the arguments with
    a row a token, ``[T, ...]``) and ``shared`` (the others: the experts'
    matrices).

    Each branch is handed what it reads of :func:`sort_slots`' three: the
    compact one ``order`` and ``group_sizes``, the fallback ``local`` (it
    sorts each share's slots for itself). The inverse of ``order``
    (:func:`slot_places`, a scatter) is not among them: nothing on a compact
    buffer reads it.

    JAX's own rule for a differentiated ``cond`` has every branch write, as
    zeros, whatever the other branch keeps for its backward pass, and hand
    what it keeps itself through the ``conditional``'s result, fusions cut
    there. Here a forward pass keeps nothing but its arguments, and the
    backward pass is a second ``cond`` whose branches each run forward again
    and then backward. Inside a recomputed layer that is the layer's
    recomputed forward pass, which then need not run unless something else
    reads the result; its operations carry the backward pass's ``op_name``
    (a ``jax.checkpoint`` round the branch would name them apart, and costs
    the step's tracing 1 to 1.5 s)."""
    return _cond(fn, compact, fits, per_token, shared, order, local, group_sizes)


def _cond(fn, compact, fits, per_token, shared, order, local, group_sizes, *d_out):
    """The ``cond``; with ``d_out``, the result's cotangent, the backward
    pass. Each branch sits under a scope of its name: the device trace says
    which ran, and ``jax.vjp`` wraps the outermost scope it meets into
    ``jvp(...)``, which must not be one that a reader looks for."""
    def compact_rows(per_token, shared):
        return fn(compact, *per_token, *shared, order, group_sizes)

    def every_slot(per_token, shared):
        return _on_every_slot(fn, compact, per_token, shared, local, group_sizes.shape[0])

    def branch(run):
        scoped = jax.named_scope(run.__name__)(run)
        if not d_out:
            return lambda: scoped(per_token, shared)
        return lambda: jax.vjp(scoped, per_token, shared)[1](*d_out)

    return jax.lax.cond(fits, branch(compact_rows), branch(every_slot))


def _either_fwd(fn, compact, fits, per_token, shared, order, local, group_sizes):
    kept = (fits, per_token, shared, order, local, group_sizes)
    return _cond(fn, compact, *kept), kept


def _either_bwd(fn, compact, kept, d_out):
    return (None,) + _cond(fn, compact, *kept, d_out) + (None, None, None)


either.defvjp(_either_fwd, _either_bwd)


@functools.partial(jax.jit, static_argnums=(0, 1, 5), inline=True)
def _on_every_slot(fn, compact, per_token, shared, local, held):
    """The fallback: ``fn`` on all ``S`` slots, there to be right, not fast,
    and never to set the step's peak memory (a ``cond``'s branches share no
    temporaries: the peak is the larger branch's). The tokens are independent
    of each other, so it is ``fn`` on a share of the tokens at a time, as many
    shares as make a share's ``T / n * k`` slots fit in ``compact`` rows: each
    share's slots are sorted for themselves by ``local``, their expert among
    the ``held`` ones as :func:`sort_slots` had it before it sorted (handed
    over, not found back from the sorted order), and every share is the whole
    layer on its tokens, so the result and the per-token gradients are those of one
    pass over ``S`` rows, the same sums in the same order, and the experts'
    gradients are float32 sums over the shares (each share's rounded to the
    products' type first: on the chip a bfloat16 step from one pass's, PERF.md
    §6, PR 35). Nothing is kept a share: the backward pass runs each again."""
    tokens, slots = per_token[0].shape[0], local.shape[0]
    shares = next(n for n in range(-(-slots // compact), tokens + 1) if tokens % n == 0)

    @jax.checkpoint
    def one_share(shared, rows):
        *per_token, local = rows
        with jax.named_scope(SORT_SCOPE):
            order, sizes, _ = sort_slots(local, 0, held)
        return fn(local.shape[0], *per_token, *shared, order, sizes)

    split = lambda a: a.reshape((shares, -1) + a.shape[1:])  # noqa: E731
    _, out = jax.lax.scan(lambda _, rows: (None, one_share(shared, rows)), None, (*map(split, per_token), split(local)))
    return out.reshape((tokens,) + out.shape[2:])


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs[rows of group g] @ rhs[g]`` for every group: ``lhs`` ``[R, K]``
    sorted by group, ``rhs`` ``[G, K, N]``, ``group_sizes`` ``int32 [G]``.
    Rows past the last group come back as zeros and pass no gradient (the
    kernels neither read nor write them, so both sides are fenced here)."""
    used = (jnp.arange(lhs.shape[0], dtype=jnp.int32) < jnp.sum(group_sizes))[:, None]
    out = jax.lax.ragged_dot(
        jnp.where(used, lhs, 0), rhs, group_sizes, preferred_element_type=lhs.dtype)
    return jnp.where(used, out, 0)
