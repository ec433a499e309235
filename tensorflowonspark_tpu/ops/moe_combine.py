"""The routed experts' way back to token order as one Pallas kernel: each
token's held slots summed out of the compact slot buffer, which is read a
window of rows at a time and nothing else.

:mod:`~tensorflowonspark_tpu.ops.grouped_matmul` sorts a layer's ``T * k``
slots by held expert and keeps the first ``C`` rows of that order, ``[C, d]``
(a chip that holds a share of the experts: ``C < T * k``). Token ``t``'s
result is the sum of its slots' rows in that buffer, at most one an expert.
The sort is stable, so **inside one expert's group the rows stand in ascending
token order**: the rows that a tile of ``tile`` consecutive tokens needs from
one expert are one contiguous range of the buffer. :func:`work_list` (plain
XLA on small int32 arrays: a count a tile and expert, cumulated) turns the
layer's ``token_of_row`` and ``group_sizes`` into a list of *steps*, each one
:data:`WINDOW`-row aligned window of the buffer for one tile and expert, and
the kernel walks that list as its grid (``ragged_dot``'s and ``megablox``'s
scheme): a step brings its window ``[128, d]`` into VMEM, builds ``onehot[tile,
128] = (the tile's token ids == token_of_row[window]) & (row in the range)``
and adds ``onehot @ window`` into the tile's float32 accumulator on the MXU;
the tile is written once, in the buffer's type. The one-hot is exact in any
type and an output row receives at most one term a step, so the sum is a plain
float32 sum of the token's rows in held-expert order. No operation a row, no
read-modify-write in HBM, no walk over ``T * k``; an expert with no slot of
a tile costs no step, a crowded one only more steps (the list's length is the
device's own count; its static bound, ``C / 128 + tiles * held``, holds
whatever the routing).

A finite buffer is assumed (a row outside a step's range is multiplied by
zero, not skipped). ``pallas_call`` has no differentiation rule: the callers
(:func:`~tensorflowonspark_tpu.ops.grouped_matmul.slots_to_tokens`,
:func:`~tensorflowonspark_tpu.ops.grouped_matmul.rows_to_slots`) carry the
``jax.custom_vjp``. ``interpret=True`` runs the kernel in the Pallas
interpreter (the CPU tests). A Mosaic call has no partitioning rule: on a mesh
the caller runs it under a ``shard_map``, the columns split over ``tp`` and
every other operand whole on every chip (``grouped_matmul._sum_over_slots``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of the buffer a step reads: the MXU's contraction
WINDOW = 128
#: tokens a tile at most. The one-hot product costs 2 * 128 * d flops a token
#: and held expert whatever the tile, the windows' reads halve as the tile
#: doubles (the two meet near 240 tokens on a v5e), and a step costs its third
#: of a microsecond: 256 / 512 / 1024 read 1.30 / 1.13 / 1.23 ms a call at
#: ``sdar-30b-a3b``'s shape, 0.49 / 0.43 / 0.45 at ``laguna-s-2-1``'s, 0.57 /
#: 0.53 / 0.59 at ``xing4-a4b``'s (PERF.md §6, PR 42)
_MOST_TOKENS = 512
#: what the kernel may take of a v5e's 128 MiB of VMEM, and what its accumulator
#: and double-buffered blocks are sized to stay under (``ops/hyper_connection.py``'s)
_VMEM_LIMIT = 96 * 2 ** 20
_VMEM_BLOCKS = 40 * 2 ** 20


def token_tile(tokens):
    """Tokens a tile of the kernel's grid."""
    return min(tokens, _MOST_TOKENS)


def _block_d(tile, d, itemsize):
    """Columns a block, the most that divide ``d`` in whole lanes and keep the
    float32 accumulator, the output block and the window (the last two
    double-buffered) under the kernel's VMEM: all of ``d`` at the cells' widths."""
    fits = lambda block: tile * block * (4 + 2 * itemsize) + 2 * WINDOW * block * itemsize <= _VMEM_BLOCKS  # noqa: E731
    blocks = [d] + [b for b in range(d - d % 128, 0, -128) if d % b == 0]
    return next((b for b in blocks if fits(b)), blocks[-1])


def work_list(token_of_row, group_sizes, tokens, tile):
    """The kernel's steps, ``int32`` all: ``(tile_of_step, window_of_step,
    first_row, last_row, steps)``, the first four ``[bound]`` long, ``steps``
    (``[1]``) how many of them are work. Step ``s`` adds to token tile
    ``tile_of_step[s]`` the rows ``first_row[s] <= r < last_row[s]`` (one held
    expert's slots of that tile's tokens) that lie in window
    ``window_of_step[s]`` of the buffer. Steps go tile by tile, expert by
    expert, window by window; a tile with no held slot gets one step with an
    empty range, which zeroes it. ``token_of_row`` (``[C]``, ``C`` a multiple
    of :data:`WINDOW`) names the token of every buffer row, ``group_sizes``
    (``[held]``) counts each held expert's rows; rows past the last group
    are in no range."""
    rows, held = token_of_row.shape[0], group_sizes.shape[0]
    tiles = -(-tokens // tile)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    row = jnp.arange(rows, dtype=jnp.int32)[:, None]
    of_expert = ((row >= starts[None, :]) & (row < ends[None, :])).astype(jnp.bfloat16)  # [C, held]
    of_tile = (token_of_row[:, None] // tile == jnp.arange(tiles, dtype=jnp.int32)[None, :]).astype(jnp.bfloat16)
    # rows a tile and expert: one small product in place of a scatter-add (exact: 0/1 terms, float32 sums under 2**24)
    count = jnp.einsum("rt,re->te", of_tile, of_expert, preferred_element_type=jnp.float32).astype(jnp.int32)
    first = (starts[None, :] + jnp.cumsum(count, axis=0) - count).reshape(-1)  # [tiles * held], tile-major
    last = first + count.reshape(-1)
    windows = jnp.where(last > first, (last - 1) // WINDOW - first // WINDOW + 1, 0).reshape(tiles, held)
    lone = (jnp.sum(windows, axis=1, keepdims=True) == 0) & (jnp.arange(held)[None, :] == 0)  # a tile's one empty step
    windows = (windows + lone).reshape(-1)
    before = jnp.cumsum(windows)  # steps up to and including each pair's
    steps = before[-1]
    # the ranges are disjoint: a window is entered from the row before it once, and a pair starts one step more at
    # most (its range's first window, or its tile's lone empty step)
    bound = rows // WINDOW + tiles * held
    step = jnp.minimum(jnp.arange(bound, dtype=jnp.int32), steps - 1)[:, None]  # past the last: the last again, and no work
    # each step's pair as a one-hot row, and what it needs of the pair by a masked sum: no gather, an operation an element
    mine = (before - windows <= step) & (step < before)  # [bound, tiles * held]
    of_pair = lambda values: jnp.sum(jnp.where(mine, values[None, :], 0), axis=1)  # noqa: E731
    window = jnp.minimum(of_pair(first // WINDOW - (before - windows)) + step[:, 0], rows // WINDOW - 1)
    return of_pair(jnp.arange(tiles * held, dtype=jnp.int32) // held), window, of_pair(first), of_pair(last), steps.reshape(1)


def _kernel(tile_ref, window_ref, first_ref, last_ref, steps_ref, token_ref, buffer_ref, out_ref, acc_ref, *, tile):
    s, bound = pl.program_id(1), pl.num_programs(1)
    here = tile_ref[s]
    work = s < steps_ref[0]

    @pl.when((s == 0) | (tile_ref[jnp.maximum(s - 1, 0)] != here))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(work & (last_ref[s] > first_ref[s]))
    def _():
        row = window_ref[s] * WINDOW + jax.lax.broadcasted_iota(jnp.int32, (1, WINDOW), 1)
        token = jnp.where((row >= first_ref[s]) & (row < last_ref[s]), token_ref[...], -1)  # [1, WINDOW]
        onehot = here * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, WINDOW), 0) == token
        acc_ref[...] += jnp.dot(
            onehot.astype(jnp.float32).astype(buffer_ref.dtype), buffer_ref[...],
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST if buffer_ref.dtype == jnp.float32 else None)

    @pl.when(work & ((s == steps_ref[0] - 1) | (tile_ref[jnp.minimum(s + 1, bound - 1)] != here)))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tokens", "interpret"))
def combine(buffer, token_of_row, group_sizes, *, tokens, interpret=False):
    """``[tokens, d]`` in ``buffer``'s type: ``out[t]`` the float32 sum, in
    held-expert order, of the rows ``r`` of ``buffer`` (``[C, d]``) inside a
    group with ``token_of_row[r] == t``. ``buffer`` is sorted by held expert
    (``group_sizes``, ``int32 [held]``) and by token inside a group; rows
    past the last group are not read for their values. Traced once a shape
    (a step meets it at three sites a routed layer)."""
    rows, d = buffer.shape
    if rows % WINDOW:  # a block past the end would hold whatever was there; ``gm.compact_rows`` gives whole tiles of 512
        raise ValueError("moe_combine: a buffer of {} rows is no multiple of {}".format(rows, WINDOW))
    tile = token_tile(tokens)
    block_d = _block_d(tile, d, buffer.dtype.itemsize)
    listed = work_list(token_of_row, group_sizes, tokens, tile)
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(listed),
            grid=(d // block_d, listed[0].shape[0]),
            in_specs=[
                pl.BlockSpec((1, WINDOW), lambda j, s, tiles, windows, *_: (0, windows[s])),
                pl.BlockSpec((WINDOW, block_d), lambda j, s, tiles, windows, *_: (windows[s], j)),
            ],
            out_specs=pl.BlockSpec((tile, block_d), lambda j, s, tiles, *_: (tiles[s], j)),
            scratch_shapes=[pltpu.VMEM((tile, block_d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((tokens, d), buffer.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_combine",
    )(*listed, token_of_row[None, :], buffer)


def rows_fetched(token_of_row, group_sizes, tokens):
    """Buffer rows that :func:`combine`'s steps bring in for this routing
    (windows visited times :data:`WINDOW`), ``float32``: over the held slots
    it is the read's amplification."""
    return work_list(token_of_row, group_sizes, tokens, token_tile(tokens))[-1][0].astype(jnp.float32) * WINDOW
