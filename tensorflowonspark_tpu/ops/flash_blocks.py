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
blocks of a kv block) are one unbroken range. The kernels walk from the
first needed block to the last; for ids in no order a block between two
needed ones is computed too, under the full masks, which is exact.

Padding is ordered *after* every real id (:func:`_order`): a row's padded
tail then overlaps only itself, where ordering 0 first would make the last
block need every block before it.
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


def attended_blocks(segment_ids):
    """``(needed, dense)`` block counts of one packed batch as the segmented
    kernels see it: rows padded to :data:`GRANULE`, the block sizes the
    kernels pick for that length, causal. ``dense`` is the causal triangle."""
    seg = np.asarray(segment_ids)
    if not seg.size:
        return 0, 0
    pad = (-seg.shape[1]) % GRANULE
    if pad:
        seg = np.pad(seg, ((0, 0), (0, pad)))
    block_q = pick_block(seg.shape[1], SEGMENTED_BLOCK_Q)
    block_k = pick_block(seg.shape[1], SEGMENTED_BLOCK_K)
    needed = needed_blocks(seg, block_q, block_k)
    dense = causal_blocks(needed.shape[1], needed.shape[2], block_q, block_k)
    return int(needed.sum()), int(dense.sum()) * seg.shape[0]
