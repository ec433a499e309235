"""Which blocks of the flash kernels' grid a batch of segment ids needs.

The rule the kernels skip by, stated once and free of jax, so that the host
(the text plane's counters) and the jitted step (the kernels' block map in
:mod:`~tensorflowonspark_tpu.ops.flash_attention`, which passes ``xp=jnp``)
run the same lines.

A (q block, kv block) pair is **needed** when some query in the one may
attend some key in the other: they share a segment id and, under
``causal``, the key is not after the query. The rule compares each block's
[smallest id, largest id] interval: two blocks that share an id have
overlapping intervals, so for any int32 ids a needed block is never dropped;
for ids that do not decrease along the row (the text plane's: 1, 2, …, n,
then 0 for padding) overlapping intervals do share an id, so nothing
skippable is kept, and the needed kv blocks of a q block (and the needed q
blocks of a kv block) are one unbroken range. The kernels visit
the needed blocks alone; for ids in no order a block whose interval overlaps
and shares no id is computed too, under the full masks, which is exact.

Padding is ordered *after* every real id (:func:`_order`): a row's padded
tail then overlaps only itself, where ordering 0 first would make the last
block need every block before it.

**The work list.** A kernel's grid does not walk the square of blocks: its
accumulating axis walks, per batch row, one flat list of the blocks the map
needs (:func:`work_list`), outer block by outer block with the inner blocks
ascending, one packed int32 an item. The axis is as long as the batch's
longest list, at most what the shape allows (:func:`work_bound`: the causal
triangle, or the square without ``causal``; the tables' row stride), and
steps past a row's own list park on its last item.
"""

import numpy as np

#: the sequence granule ``models/transformer._flash`` pads rows to before the
#: kernels see them (and :func:`attended_blocks` pads its ids to)
GRANULE = 128

# tuned on v5e (L=4096, d=64, bf16): 512/512 runs ~1.3x faster than XLA's
# fused attention; 128/128 only ties it
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
#: the blocks of the segmented kernels, which skip by the block: finer blocks
#: skip more of a packed row and cost more grid steps. Read once on a v5e on
#: rows packed by the benchmark's document law (PERF.md §6, PR 25), two
#: forwards and a backward per call: 512/512 15.7 ms (60% of the triangle's
#: blocks needed), 512/256 24.4 ms (57%), 256/256 31.1 ms (50%); the dense
#: grid at 512/512 took 24.1 ms
SEGMENTED_BLOCK_Q = 512
SEGMENTED_BLOCK_K = 512

_INT32_MIN = np.int32(-(2 ** 31))


def pick_block(seq, preferred):
    """Largest power-of-two block ≤ preferred that divides seq (whole-array
    block for short sequences); pallas pads ragged trailing blocks with
    garbage, so blocks must tile the sequence exactly."""
    if seq <= preferred:
        return seq
    b = preferred
    while b >= 8:  # 8 = minimum sublane tile
        if seq % b == 0:
            return b
        b //= 2
    raise ValueError(
        "sequence length {} has no 8..{} block divisor; pad the sequence "
        "or use plain attention".format(seq, preferred)
    )


def _order(segment_ids, xp):
    """Ids as int32 keys that sort 1 < 2 < … < INT32_MAX < negatives < 0,
    no two ids sharing a key: ``id - 1`` read as unsigned, its top bit
    flipped so that signed comparisons order it."""
    return (segment_ids.astype(xp.int32) - xp.int32(1)) ^ _INT32_MIN


def block_bounds(segment_ids, block_q, block_k, xp=np):
    """``(q_min, q_max, k_min, k_max)``: the smallest and largest ordered id
    of every q block (``[rows, L // block_q]``) and kv block
    (``[rows, L // block_k]``) of ``segment_ids`` ``[rows, L]``."""
    key = _order(segment_ids, xp)
    rows, seq = key.shape
    per_q = key.reshape(rows, seq // block_q, block_q)
    per_k = key.reshape(rows, seq // block_k, block_k)
    return per_q.min(-1), per_q.max(-1), per_k.min(-1), per_k.max(-1)


def causal_blocks(n_q, n_k, block_q, block_k, xp=np):
    """``bool [n_q, n_k]``: blocks holding a key at or before a query (the
    causal triangle; the dense grid the kernels ran before the map)."""
    first_k = xp.arange(n_k)[None, :] * block_k
    last_q = xp.arange(n_q)[:, None] * block_q + (block_q - 1)
    return first_k <= last_q


def blocks_needed(bounds, block_q, block_k, causal=True, xp=np):
    """The rule, on :func:`block_bounds`' four tables: ``bool [rows, n_q,
    n_k]``, True where a q block's and a kv block's id intervals overlap
    and, under ``causal``, the kv block does not lie above the diagonal."""
    q_min, q_max, k_min, k_max = bounds
    needed = (q_min[:, :, None] <= k_max[:, None, :]) & (k_min[:, None, :] <= q_max[:, :, None])
    if causal:
        needed = needed & causal_blocks(q_min.shape[1], k_min.shape[1], block_q, block_k, xp)[None]
    return needed


def needed_blocks(segment_ids, block_q, block_k, causal=True):
    """``bool [rows, L // block_q, L // block_k]``: True where the kernels
    compute the block for ``segment_ids`` ``[rows, L]`` (numpy, on the host)."""
    bounds = block_bounds(np.asarray(segment_ids), block_q, block_k)
    return blocks_needed(bounds, block_q, block_k, causal)


#: an item of a work list, one int32: the outer block from bit 17, the inner
#: block in bits 3-16, then three flags
ITEM_INNER_SHIFT, ITEM_OUTER_SHIFT = 3, 17
ITEM_COMPUTE, ITEM_FIRST, ITEM_LAST = 1, 2, 4
#: the most blocks along one axis that an item can name
ITEM_BLOCKS_MOST = 1 << (ITEM_OUTER_SHIFT - ITEM_INNER_SHIFT)

_INT32_MAX = np.int32(2 ** 31 - 1)


def dense_blocks(n_q, n_k, block_q, block_k, causal=True):
    """``bool [n_q, n_k]`` (numpy): every block a call of that shape can need,
    whatever its ids: the causal triangle, or the square."""
    return causal_blocks(n_q, n_k, block_q, block_k) if causal else np.ones((n_q, n_k), bool)


def work_bound(dense):
    """The longest :func:`work_list` a row can have when its needed blocks lie
    inside ``dense`` (``bool [n_outer, n_inner]``): every block of ``dense``,
    and one item for an outer block that ``dense`` leaves none (kv blocks
    above every query when keys outnumber queries). A Python integer: it is
    the length of the kernels' accumulating grid axis."""
    return int(np.maximum(np.asarray(dense).sum(1), 1).sum())


def work_list(needed, steps, xp=np):
    """The kernels' work lists: ``(items int32 [rows, steps], lengths int32
    [rows])`` from ``needed`` ``bool [rows, n_outer, n_inner]``.

    A row's list holds its needed blocks in outer-major order, the inner
    blocks ascending, and for an outer block that needs none one item
    (inner block 0) that computes nothing, so that every outer block is
    visited and written. An item is ``outer << 17 | inner << 3 | flags``:
    :data:`ITEM_COMPUTE` (a needed block), :data:`ITEM_FIRST` and
    :data:`ITEM_LAST` of its outer block (zero the block accumulators; write
    them out). Entries past a row's length repeat its last item without
    flags: a grid step there names the blocks already resident and does
    nothing (a parked step). ``steps`` must be at least the longest list
    (:func:`work_bound` of the shape is)."""
    rows, n_outer, n_inner = needed.shape
    inner = xp.arange(n_inner, dtype=xp.int32)[None, None, :]
    outer = xp.arange(n_outer, dtype=xp.int32)[None, :, None]
    emitted = needed | (~needed.any(2, keepdims=True) & (inner == 0))
    nth = xp.cumsum(emitted.astype(xp.int32), axis=2)
    flags = (needed * xp.int32(ITEM_COMPUTE) + (nth == 1) * xp.int32(ITEM_FIRST)
             + (nth == nth[:, :, -1:]) * xp.int32(ITEM_LAST))
    item = (outer << ITEM_OUTER_SHIFT) | (inner << ITEM_INNER_SHIFT) | flags
    # an item's value orders it: sorting the emitted ones to the front is the compaction
    items = xp.sort(xp.where(emitted, item, _INT32_MAX).reshape(rows, n_outer * n_inner), axis=1)[:, :steps]
    if steps > items.shape[1]:
        items = xp.concatenate([items, xp.full((rows, steps - items.shape[1]), _INT32_MAX, xp.int32)], axis=1)
    lengths = emitted.sum((1, 2)).astype(xp.int32)
    at = xp.arange(steps, dtype=xp.int32)[None, :]
    parked = xp.take_along_axis(items, lengths[:, None] - 1, axis=1) & ~xp.int32(ITEM_COMPUTE | ITEM_FIRST | ITEM_LAST)
    return xp.where(at < lengths[:, None], items, parked), lengths


def attended_blocks(segment_ids):
    """``(needed, dense, steps)`` counts of one packed batch as the segmented
    kernels see it: rows padded to :data:`GRANULE`, the block sizes the
    kernels pick for that length, causal. ``needed`` blocks are computed;
    ``dense`` is the causal triangle, a row's :func:`work_bound`; ``steps``
    are the grid steps a kernel takes a head: every row walks as many as the
    batch's longest :func:`work_list` has items, and a row with fewer parks
    for the rest."""
    seg = np.asarray(segment_ids)
    if not seg.size:
        return 0, 0, 0
    pad = (-seg.shape[1]) % GRANULE
    if pad:
        seg = np.pad(seg, ((0, 0), (0, pad)))
    block_q = pick_block(seg.shape[1], SEGMENTED_BLOCK_Q)
    block_k = pick_block(seg.shape[1], SEGMENTED_BLOCK_K)
    needed = needed_blocks(seg, block_q, block_k)
    dense = work_bound(causal_blocks(needed.shape[1], needed.shape[2], block_q, block_k))
    _, lengths = work_list(needed, dense)
    return int(needed.sum()), dense * seg.shape[0], int(lengths.max()) * seg.shape[0]
