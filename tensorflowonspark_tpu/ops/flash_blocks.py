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

**A second rule: block diffusion.** A row trained by diffusion over blocks
holds every document twice, a clean copy and a noised one, and its mask is
neither causal nor a window. A position carries its document's id and a label
``2 * block + half`` (``block``: its diffusion block, counted from the
document's start; ``half``: 0 clean, 1 noised). A query sees a key of its own
document when both are noised and of one block, or when the key is clean and
of a block before the query's (a noised query) or not after it (a clean one);
no clean query sees a noised key, and padding sees and is seen by nothing.
:func:`bd_marks` turns ids and labels into four int32 marks a position, under
which that is two comparisons and an equality whatever the row's layout: a
key's ``key`` (its document and block in one number, the noised copies moved
up by :data:`BD_NOISED`), and a query's ``[lo, hi]`` (the clean keys it sees:
from its document's first block to its own, less one where it is noised) and
``own`` (the noised keys it sees). :func:`bd_blocks_needed` is the rule on
block bounds, from each block's smallest and largest marks over its real
positions; on rows whose two halves each hold the documents in order and
fill whole blocks (the text plane's ``[clean ; noised]`` rows) it keeps
exactly the blocks with a visible pair, and on any other marks never drops
one. The lists' stride is then the square: which side of the diagonal a
needed block lies on is the layout's business, not the rule's.

**A third rule: a window.** ``rule="window"`` with a static ``window`` is the
first rule, causal, with one more condition: key ``j`` is visible to query
``i`` when both are of one document, ``j <= i`` and ``i - j < window`` (the
window counts the query itself). A q block's first needed kv block then moves
with it: a block pair is needed when its id intervals overlap, it is not
above the diagonal and its nearest pair, the q block's first position and the
kv block's last, lies inside the window (:func:`window_blocks`). For ids that
do not decrease along the row that keeps exactly the blocks with a visible
pair (the q block's first position and the kv block's last then share a
document whenever the intervals overlap), for any ids it drops none, and the
lists' stride is the band (:func:`dense_blocks`), not the triangle.
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


def window_blocks(n_q, n_k, block_q, block_k, window, xp=np):
    """``bool [n_q, n_k]``: blocks holding a key at or before a query and
    fewer than ``window`` positions behind it (the band a windowed call can
    need, whatever its ids): inside the triangle, and the q block's first
    position within ``window`` of the kv block's last."""
    last_k = xp.arange(n_k)[None, :] * block_k + (block_k - 1)
    first_q = xp.arange(n_q)[:, None] * block_q
    return causal_blocks(n_q, n_k, block_q, block_k, xp) & (first_q - last_k < window)


def blocks_needed(bounds, block_q, block_k, causal=True, xp=np):
    """The rule, on :func:`block_bounds`' four tables: ``bool [rows, n_q,
    n_k]``, True where a q block's and a kv block's id intervals overlap
    and, under ``causal``, the kv block does not lie above the diagonal."""
    q_min, q_max, k_min, k_max = bounds
    needed = (q_min[:, :, None] <= k_max[:, None, :]) & (k_min[:, None, :] <= q_max[:, :, None])
    if causal:
        needed = needed & causal_blocks(q_min.shape[1], k_min.shape[1], block_q, block_k, xp)[None]
    return needed


def window_blocks_needed(bounds, block_q, block_k, window, xp=np):
    """The window rule on :func:`block_bounds`' four tables: the blocks whose
    id intervals overlap, inside the band of :func:`window_blocks`."""
    band = window_blocks(bounds[0].shape[1], bounds[2].shape[1], block_q, block_k, window, xp)
    return blocks_needed(bounds, block_q, block_k, causal=False, xp=xp) & band[None]


def needed_blocks(segment_ids, block_q, block_k, causal=True, labels=None, window=None):
    """``bool [rows, L // block_q, L // block_k]``: True where the kernels
    compute the block for ``segment_ids`` ``[rows, L]`` (numpy, on the host);
    with ``labels``, by the block-diffusion rule; with ``window``, by the
    window rule."""
    if labels is not None:
        return bd_blocks_needed(bd_bounds(bd_marks(np.asarray(segment_ids), np.asarray(labels)), block_q, block_k))
    bounds = block_bounds(np.asarray(segment_ids), block_q, block_k)
    if window is not None:
        return window_blocks_needed(bounds, block_q, block_k, window)
    return blocks_needed(bounds, block_q, block_k, causal)


def window_mask(segment_ids, window, xp=np):
    """The window rule written out, ``bool [rows, L, L]`` (query, key): what
    the kernels are held to, and the mask of the paths that materialise one
    (``plain`` attention, small rows only). Padding (id 0) sees and is seen
    by nothing."""
    seg = segment_ids.astype(xp.int32)
    at = xp.arange(seg.shape[1], dtype=xp.int32)
    behind = at[None, :, None] - at[None, None, :]
    return (seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] > 0) & (behind >= 0) & (behind < window)


def visible_pairs(segment_ids, window=None):
    """Query-key pairs the causal rule (with ``window``: the window rule)
    shows in rows of ids that do not decrease along the row, as the text
    plane's (numpy; padding, id 0, shows none): a document's ``p``-th token
    sees ``min(p, window)`` keys, itself among them."""
    seg = np.asarray(segment_ids)
    at = np.arange(seg.shape[1], dtype=np.int64)[None, :]
    starts = np.concatenate([np.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    seen = at - np.maximum.accumulate(np.where(starts, at, 0), axis=1) + 1
    if window is not None:
        seen = np.minimum(seen, window)
    return int(seen[seg > 0].sum())


#: the rules a call may mask and skip by: ``causal`` is the module's first
#: (with or without ids, with or without the triangle), ``block_diffusion``
#: the second, ``window`` the third (causal, and within a static window)
RULES = ("causal", "block_diffusion", "window")

#: what a noised key's mark lies above its clean copy's, and how a mark packs
#: document and block: ``id << BD_BLOCK_BITS | block``
BD_BLOCK_BITS = 16
BD_NOISED = np.int32(1 << 30)
_INT32_MAX = np.int32(2 ** 31 - 1)


def bd_marks(segment_ids, labels, xp=np):
    """``(lo, hi, own, key)``, int32 ``[rows, L]`` each, of a block-diffusion
    row: query ``i`` sees key ``j`` when ``lo[i] <= key[j] <= hi[i]`` or
    ``key[j] == own[i]``. ``labels`` is ``2 * block + half``; a document has
    at most ``2 ** 16`` blocks and a row at most ``2 ** 14 - 1`` documents.
    Padding (id 0) gets an empty range, an ``own`` no key has and a ``key``
    no query asks for."""
    seg, labels = segment_ids.astype(xp.int32), labels.astype(xp.int32)
    noised = labels & 1
    first = seg << BD_BLOCK_BITS
    at = first | (labels >> 1)
    real = seg > 0
    lo = xp.where(real, first, xp.int32(0))
    hi = xp.where(real, at - noised, xp.int32(-1))
    own = xp.where(real, at + noised * BD_NOISED, xp.int32(-2))
    return lo, hi, own, xp.where(real, own, xp.int32(-3))


def bd_mask(segment_ids, labels, xp=np):
    """The rule written out, ``bool [rows, L, L]`` (query, key): what the
    marks and the kernels are held to, and the mask of the paths that
    materialise one (``plain`` attention, small rows only)."""
    seg, labels = segment_ids.astype(xp.int32), labels.astype(xp.int32)
    block, noised = labels >> 1, labels & 1
    q = lambda t: t[:, :, None]  # noqa: E731
    k = lambda t: t[:, None, :]  # noqa: E731
    same = (q(seg) == k(seg)) & (q(seg) > 0)
    among_noised = (q(noised) == 1) & (k(noised) == 1) & (q(block) == k(block))
    clean_before = (k(noised) == 0) & (k(block) <= q(block) - q(noised))
    return same & (among_noised | clean_before)


def bd_bounds(marks, block_q, block_k, xp=np):
    """The six tables :func:`bd_blocks_needed` compares: per q block the
    smallest ``lo``, largest ``hi`` and the ``[smallest, largest]`` ``own``,
    per kv block the ``[smallest, largest]`` ``key``, over real positions
    alone (a block of padding gets an interval nothing overlaps)."""
    lo, hi, own, key = marks
    rows, seq = key.shape
    real_q = (own >= 0).reshape(rows, seq // block_q, block_q)
    real_k = (key >= 0).reshape(rows, seq // block_k, block_k)

    def least(x, real, block):
        return xp.where(real, x.reshape(rows, seq // block, block), _INT32_MAX).min(-1)

    def most(x, real, block):
        return xp.where(real, x.reshape(rows, seq // block, block), xp.int32(-4)).max(-1)

    return (least(lo, real_q, block_q), most(hi, real_q, block_q), least(own, real_q, block_q),
            most(own, real_q, block_q), least(key, real_k, block_k), most(key, real_k, block_k))


def bd_blocks_needed(bounds):
    """The block-diffusion rule on :func:`bd_bounds`' tables: ``bool [rows,
    n_q, n_k]``, True where a kv block's keys overlap the clean keys some
    query of the q block sees, or the noised ones."""
    lo, hi, own_min, own_max = (t[:, :, None] for t in bounds[:4])
    key_min, key_max = (t[:, None, :] for t in bounds[4:])
    return ((key_min <= hi) & (key_max >= lo)) | ((key_min <= own_max) & (key_max >= own_min))


#: an item of a work list, one int32: the outer block from bit 17, the inner
#: block in bits 3-16, then three flags
ITEM_INNER_SHIFT, ITEM_OUTER_SHIFT = 3, 17
ITEM_COMPUTE, ITEM_FIRST, ITEM_LAST = 1, 2, 4
#: the most blocks along one axis that an item can name
ITEM_BLOCKS_MOST = 1 << (ITEM_OUTER_SHIFT - ITEM_INNER_SHIFT)


def dense_blocks(n_q, n_k, block_q, block_k, causal=True, window=None):
    """``bool [n_q, n_k]`` (numpy): every block a call of that shape can need,
    whatever its ids: the window's band, the causal triangle, or the square."""
    if window is not None:
        return window_blocks(n_q, n_k, block_q, block_k, window)
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


def attended_blocks(segment_ids, labels=None, window=None):
    """``(needed, dense, steps)`` counts of one packed batch as the segmented
    kernels see it: rows padded to :data:`GRANULE`, the block sizes the
    kernels pick for that length, causal; with ``labels``, the rows as a
    block-diffusion model reads them (both copies) under that rule; with
    ``window``, under the window rule.
    ``needed`` blocks are computed; ``dense`` is the row's causal triangle
    (under every rule: what a kernel that knew only the triangle would
    walk); ``steps`` are the grid steps a kernel takes a head: every row
    walks as many as the batch's longest :func:`work_list` has items, and a
    row with fewer parks for the rest."""
    seg = np.asarray(segment_ids)
    if not seg.size:
        return 0, 0, 0
    pad = (-seg.shape[1]) % GRANULE
    if pad:
        seg = np.pad(seg, ((0, 0), (0, pad)))
        labels = None if labels is None else np.pad(np.asarray(labels), ((0, 0), (0, pad)))
    block_q = pick_block(seg.shape[1], SEGMENTED_BLOCK_Q)
    block_k = pick_block(seg.shape[1], SEGMENTED_BLOCK_K)
    needed = needed_blocks(seg, block_q, block_k, labels=labels, window=window)
    n_q, n_k = needed.shape[1:]
    dense = work_bound(causal_blocks(n_q, n_k, block_q, block_k))
    _, lengths = work_list(
        needed, work_bound(dense_blocks(n_q, n_k, block_q, block_k, causal=labels is None, window=window)))
    return int(needed.sum()), dense * seg.shape[0], int(lengths.max()) * seg.shape[0]


def block_pairs(segment_ids):
    """Query-key pairs inside one block of the segmented kernels for rows
    ``[rows, L]`` (as :func:`attended_blocks` pads and blocks them): what a
    computed block computes, visible or not."""
    seq = np.asarray(segment_ids).shape[1]
    seq += (-seq) % GRANULE
    return pick_block(seq, SEGMENTED_BLOCK_Q) * pick_block(seq, SEGMENTED_BLOCK_K)
