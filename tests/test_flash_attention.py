"""Flash-attention kernel numerics vs plain attention (pallas interpret mode)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.ops import flash_attention as fa
from tensorflowonspark_tpu.ops import flash_blocks
from tensorflowonspark_tpu.ops.flash_attention import flash_attention
from tensorflowonspark_tpu.parallel.ring_attention import plain_attention


def _qkv(b=2, h=2, l=256, d=64, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((b, h, l, d)), jnp.float32) for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_plain(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    expected = plain_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)


def test_multi_block_grid():
    # seq 256 with 64-blocks → 4x4 kv/q grid, exercises accumulator reuse
    q, k, v = _qkv(l=256, seed=1)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    expected = plain_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_plain(causal):
    q, k, v = _qkv(l=128, seed=2)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True) ** 2).sum()

    def loss_plain(q, k, v):
        return (plain_attention(q, k, v, causal=causal) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_plain = jax.grad(loss_plain, argnums=(0, 1, 2))(q, k, v)
    for gf, gp, name in zip(g_flash, g_plain, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gp), atol=5e-4,
            err_msg="d{} mismatch".format(name),
        )


def test_bfloat16_forward():
    q, k, v = _qkv(l=128, seed=3)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out = flash_attention(q, k, v, causal=True, interpret=True)
    assert out.dtype == jnp.bfloat16
    expected = plain_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32), atol=0.05
    )


# -- the block map: segmented kernels compute only the blocks the packing needs

_L, _BLOCK, _D = 256, 64, 32


def _ids(lengths, seq=_L):
    """Packed ids 1, 2, … for documents of ``lengths``, a zero tail after."""
    row = np.zeros(seq, np.int32)
    at = 0
    for i, n in enumerate(lengths, start=1):
        row[at:at + n] = i
        at += n
    assert at <= seq
    return row


def _arbitrary_ids(seed, seq=_L):
    """Runs of ids in no order: repeats far apart, zeros inside, negatives
    and the int32 extremes. The map may only over-approximate on these."""
    rng = np.random.default_rng(seed)
    pool = np.array([0, 1, 2, 3, 7, -1, -5, 2 ** 31 - 1, -(2 ** 31)], np.int64)
    row, at = np.zeros(seq, np.int64), 0
    while at < seq:
        n = int(rng.integers(1, 90))
        row[at:at + n] = rng.choice(pool)
        at += n
    return row.astype(np.int32)


ROWS = {
    "long_document": _ids([150, 40, 66]),  # one document over more than two blocks
    "short_documents": _ids([20, 9, 31, 17, 25, 30, 12, 28, 22, 19, 27, 16]),
    "boundary_on_block_edge": _ids([64, 128, 64]),
    "padded_tail": _ids([70, 50, 8]),  # the tail fills more than a block
    "all_padding": _ids([]),
    "single_document": _ids([_L]),
    "arbitrary_order": _arbitrary_ids(5),
    "arbitrary_order_2": _arbitrary_ids(6),
}


@functools.lru_cache(maxsize=None)
def _flash_fn(causal, block_q=_BLOCK, block_k=_BLOCK):
    """flash and its gradients for a cotangent ``do``, jitted once per
    setting so that the cases of one shape share a compilation."""

    def run(q, k, v, seg, do):
        o, vjp = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, segment_ids=seg, block_q=block_q, block_k=block_k, interpret=True),
            q, k, v)
        return (o,) + vjp(do)

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _plain_fn(causal):
    def run(q, k, v, seg, do):
        o, vjp = jax.vjp(lambda q, k, v: plain_attention(q, k, v, causal=causal, segment_ids=seg), q, k, v)
        return (o,) + vjp(do)

    return jax.jit(run)


def _operands(rows, heads=2, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    shape = (len(rows), heads, rows.shape[1], _D)
    return tuple(jnp.asarray(rng.standard_normal(shape), dtype) for _ in range(4))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("kind", list(ROWS) + ["all_rows_in_one_batch"])
def test_segmented_matches_plain(kind, causal):
    """Forward, dq, dk and dv of the mapped kernels against the plain masked
    reference, finite everywhere (an all-padding row included)."""
    rows = np.stack(list(ROWS.values())) if kind == "all_rows_in_one_batch" else ROWS[kind][None]
    q, k, v, do = _operands(rows, seed=len(kind))
    got = _flash_fn(causal)(q, k, v, jnp.asarray(rows), do)
    want = _plain_fn(causal)(q, k, v, jnp.asarray(rows), do)
    for g, w, name in zip(got, want, ("o", "dq", "dk", "dv")):
        assert np.isfinite(np.asarray(g)).all(), name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-4, err_msg=name)


@pytest.mark.parametrize("blocks", [(64, 64), (64, 32), (32, 64)])
def test_segmented_matches_plain_at_unequal_blocks(blocks):
    rows = np.stack([ROWS["long_document"], ROWS["short_documents"], ROWS["padded_tail"]])
    q, k, v, do = _operands(rows, seed=11)
    got = _flash_fn(True, *blocks)(q, k, v, jnp.asarray(rows), do)
    want = _plain_fn(True)(q, k, v, jnp.asarray(rows), do)
    for g, w, name in zip(got, want, ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-4, err_msg=name)


def _case(l_q, l_k, d, d_v, seed, b=1, h=2):
    """q, k, v and a cotangent at lengths and widths of their own."""
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    return draw(b, h, l_q, d), draw(b, h, l_k, d), draw(b, h, l_k, d_v), draw(b, h, l_q, d_v)


#: (mask, l_q, l_k): ids ride one table for queries and keys, so the fence
#: wants equal lengths; 128 x 256 causal leaves kv blocks 2 and 3 to no q block
_BACKWARD_CASES = [
    ("segmented_causal", 256, 256),
    ("causal", 256, 256), ("causal", 128, 256), ("causal", 256, 128),
    ("neither", 256, 256), ("neither", 128, 256), ("neither", 256, 128),
]


@pytest.mark.parametrize("mask,l_q,l_k", _BACKWARD_CASES, ids=["{}-{}x{}".format(*c) for c in _BACKWARD_CASES])
@pytest.mark.parametrize("widths", [(64, 64), (192, 128)], ids=["64/64", "192/128"])
def test_one_backward_kernel_matches_plain(widths, mask, l_q, l_k):
    """dq, dk and dv of the one kv-major kernel against plain attention:
    equal and unequal q/k and v widths, every mask, ``l_q != l_k``."""
    q, k, v, do = _case(l_q, l_k, *widths, seed=l_q + l_k + widths[0])
    seg = jnp.asarray(_ids([100, 60, 70])[None]) if mask == "segmented_causal" else None
    causal = mask != "neither"
    got = _flash_fn(causal)(q, k, v, seg, do)
    want = _plain_fn(causal)(q, k, v, seg, do)
    for g, w, name in zip(got, want, ("o", "dq", "dk", "dv")):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-3, err_msg=name)
    if causal and l_q < l_k:
        # kv blocks above every query: walked by no q block, written as zeros
        assert not np.asarray(got[2])[:, :, l_q:].any() and not np.asarray(got[3])[:, :, l_q:].any()


@pytest.fixture
def patch_rule(monkeypatch):
    """Replace ``flash_blocks.blocks_needed`` for one test. The device map is
    jitted on its own and keeps its traces: without dropping them a patched
    rule is never run (the test would compare the real map with itself), and
    a later test would run the patched one."""

    def patch(rule):
        monkeypatch.setattr(flash_blocks, "blocks_needed", rule)
        fa._block_map.clear_cache()

    fa._block_map.clear_cache()
    yield patch
    monkeypatch.undo()
    fa._block_map.clear_cache()


def _block_rows(block, size=_BLOCK):
    return slice(block * size, (block + 1) * size)


@pytest.mark.parametrize("kind", ["long_document", "short_documents", "padded_tail", "arbitrary_order"])
def test_skipped_blocks_are_not_read(kind):
    """Poison: NaN in the K/V blocks a q block's map skips leaves that q
    block's output as it was, and its dq, which the kv-major backward kernel
    sums; NaN in the Q/dO blocks a kv block's map skips leaves its dk and
    dv. (A kernel that multiplied a skipped block by p = 0 would spread the
    NaN.)"""
    row = ROWS[kind]
    needed = flash_blocks.needed_blocks(row[None], _BLOCK, _BLOCK)[0]
    assert not needed.all() and needed.any(1).all()

    # the kernels' lists hold the needed blocks alone, q-major and kv-major
    # (dq as well) the same pairs: for ids in no order not even a block
    # between two needed ones is read
    q, k, v, do = _operands(row[None], heads=1, seed=3)
    seg = jnp.asarray(row[None])
    run = _flash_fn(True)
    o, dq, dk, dv = (np.asarray(t) for t in run(q, k, v, seg, do))

    def poisoned(t, blocks):
        t = np.array(t)
        for block in blocks:
            t[:, :, _block_rows(block)] = np.nan
        return jnp.asarray(t)

    n = _L // _BLOCK
    for iq in range(n):
        skipped = [ik for ik in range(n) if not needed[iq, ik]]
        o_p, dq_p, _, _ = run(q, poisoned(k, skipped), poisoned(v, skipped), seg, do)
        np.testing.assert_array_equal(np.asarray(o_p)[:, :, _block_rows(iq)], o[:, :, _block_rows(iq)])
        np.testing.assert_array_equal(np.asarray(dq_p)[:, :, _block_rows(iq)], dq[:, :, _block_rows(iq)])
    for ik in range(n):
        skipped = [iq for iq in range(n) if not needed[iq, ik]]
        _, _, dk_p, dv_p = run(poisoned(q, skipped), k, v, seg, poisoned(do, skipped))
        np.testing.assert_array_equal(np.asarray(dk_p)[:, :, _block_rows(ik)], dk[:, :, _block_rows(ik)])
        np.testing.assert_array_equal(np.asarray(dv_p)[:, :, _block_rows(ik)], dv[:, :, _block_rows(ik)])


def test_unsegmented_causal_does_not_read_above_the_diagonal():
    q, k, v, do = _operands(np.zeros((1, _L), np.int32), heads=1, seed=4)
    run = _flash_fn(True)
    clean = run(q, k, v, None, do)
    tail = np.array(k)
    tail[:, :, _BLOCK:] = np.nan  # q block 0 needs kv block 0 alone
    dirty = run(q, jnp.asarray(tail), v, None, do)
    np.testing.assert_array_equal(np.asarray(dirty[0])[:, :, :_BLOCK], np.asarray(clean[0])[:, :, :_BLOCK])
    np.testing.assert_array_equal(np.asarray(dirty[1])[:, :, :_BLOCK], np.asarray(clean[1])[:, :, :_BLOCK])


def _random_packing(rng, seq):
    lengths = []
    while sum(lengths) < seq:
        lengths.append(int(min(rng.lognormal(3.0, 1.1) + 1, seq - sum(lengths))))
    if rng.random() < 0.7:  # most rows end in padding
        lengths = lengths[:-1]
    return _ids(lengths, seq)


def _brute_force(rows, block_q, block_k, causal=True):
    """Blocks that hold a pair the kernels' masks let through."""
    seq = rows.shape[1]
    pairs = rows[:, :, None] == rows[:, None, :]
    if causal:
        pairs &= np.tril(np.ones((seq, seq), bool))[None]
    return pairs.reshape(len(rows), seq // block_q, block_q, seq // block_k, block_k).any((2, 4))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(64, 64), (64, 32), (32, 64), (16, 16)])
def test_needed_blocks_against_brute_force(blocks, causal):
    """Exact for packed ids; never short of a needed block for any ids."""
    rng = np.random.default_rng(sum(blocks) + causal)
    packed = np.stack([_random_packing(rng, 512) for _ in range(40)])
    np.testing.assert_array_equal(
        flash_blocks.needed_blocks(packed, *blocks, causal=causal), _brute_force(packed, *blocks, causal))
    wild = np.stack([_arbitrary_ids(seed, 512) for seed in range(40)])
    kept = flash_blocks.needed_blocks(wild, *blocks, causal=causal)
    assert (kept | ~_brute_force(wild, *blocks, causal)).all()
    assert not kept.all()


def test_padding_sorts_after_every_real_id():
    """Ids 1, 2, …, n, 0, 0: the padded last block needs itself alone, not
    every block before it."""
    needed = flash_blocks.needed_blocks(_ids([64, 64, 64])[None], 64, 64)[0]
    np.testing.assert_array_equal(needed, np.eye(4, dtype=bool))


def _decoded(items):
    """``(outer, inner, compute, first, last)`` arrays of work-list items."""
    items = np.asarray(items)
    inner = (items >> flash_blocks.ITEM_INNER_SHIFT) & (flash_blocks.ITEM_BLOCKS_MOST - 1)
    flag = lambda bit: (items & bit) != 0  # noqa: E731
    return (items >> flash_blocks.ITEM_OUTER_SHIFT, inner, flag(flash_blocks.ITEM_COMPUTE),
            flag(flash_blocks.ITEM_FIRST), flag(flash_blocks.ITEM_LAST))


def _check_work_list(needed, steps):
    """One orientation of ``needed`` ``[rows, n_outer, n_inner]`` against the
    list's contract, row by row, by brute force."""
    items, lengths = flash_blocks.work_list(needed, steps)
    assert items.dtype == np.int32 and items.shape == (len(needed), steps)
    n_outer, n_inner = needed.shape[1:]
    for row, row_items, length in zip(needed, items, lengths):
        assert n_outer <= length <= steps
        outer, inner, compute, first, last = (t[:length] for t in _decoded(row_items))
        # outer-major, inner ascending, no item twice
        assert (np.diff(outer * n_inner + inner) > 0).all()
        # every needed block once, and nothing else computed
        visited = np.zeros_like(row)
        visited[outer[compute], inner[compute]] = True
        np.testing.assert_array_equal(visited, row)
        assert compute.sum() == row.sum()
        # every outer block at least once; one that needs nothing has one item that computes nothing
        np.testing.assert_array_equal(np.unique(outer), np.arange(n_outer))
        idle = ~compute
        np.testing.assert_array_equal(np.sort(outer[idle]), np.flatnonzero(~row.any(1)))
        assert (first[idle] & last[idle]).all()
        # first / last of an outer block are where the outer block changes
        np.testing.assert_array_equal(first, np.r_[True, np.diff(outer) != 0])
        np.testing.assert_array_equal(last, np.r_[np.diff(outer) != 0, True])
        # past the list: the last item again, without flags (a parked step)
        parked = row_items[length:]
        assert (parked == (row_items[length - 1] & ~np.int32(7))).all()


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(64, 64), (64, 32), (32, 64), (16, 16)])
def test_work_list_against_brute_force(blocks, causal):
    """Both kernels' lists (q-major, kv-major) over packed rows, ids in no
    order and maps with starved blocks: every needed block once, outer-major
    with the inner blocks ascending, every outer block at least once, the
    three bits, never longer than the shape's bound."""
    rng = np.random.default_rng(sum(blocks) + 2 * causal)
    rows = np.stack([_random_packing(rng, 512) for _ in range(12)] + [_ids([], 512), _ids([512], 512)]
                    + [_arbitrary_ids(seed, 512) for seed in range(12)])
    needed = flash_blocks.needed_blocks(rows, *blocks, causal=causal)
    dense = flash_blocks.dense_blocks(*needed.shape[1:], *blocks, causal)
    holes = needed & (rng.random(needed.shape) < 0.5)  # no ids give these: blocks and whole rows of the map starved
    holes[0, 1], holes[1, :, 2] = False, False
    for some in (needed, holes):
        _check_work_list(some, flash_blocks.work_bound(dense))
        _check_work_list(some.swapaxes(1, 2), flash_blocks.work_bound(dense.T))
        _check_work_list(some, some.shape[1] * some.shape[2] + 5)  # a bound longer than the square


_BOUNDS = [
    # n_q, n_k, causal: forward steps, backward steps
    ((8, 8, True), (36, 36)), ((16, 16, True), (136, 136)), ((32, 32, True), (528, 528)),
    ((160, 160, True), (12880, 12880)), ((8, 8, False), (64, 64)), ((2, 4, False), (8, 8)),
    ((2, 4, True), (3, 5)),  # kv blocks 2 and 3 lie above every query: one idle item each in the backward
    ((4, 2, True), (7, 7)),
]


@pytest.mark.parametrize("shape,steps", _BOUNDS, ids=["{}x{}-{}".format(*s) for s, _ in _BOUNDS])
def test_grid_axis_is_as_long_as_the_triangle(shape, steps):
    """The accumulating grid axis: the blocks ``causal_blocks`` marks (and
    one step for an outer block it leaves none), the square without
    ``causal``: what ``flash_blocks_dense_total`` counts a row."""
    n_q, n_k, causal = shape
    assert fa._steps(n_q, n_k, 512, 512, causal) == steps
    if causal and n_q == n_k:
        assert steps[0] == int(flash_blocks.causal_blocks(n_q, n_k, 512, 512).sum()) == n_q * (n_q + 1) // 2


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(64, 64), (64, 32)])
def test_device_map_is_the_host_rule(blocks, causal):
    """The jitted step's map and the host's counters state one rule: the
    needed blocks equal block for block, and the two work lists the kernels
    walk equal the numpy rule's item for item."""
    rng = np.random.default_rng(9)
    rows = np.stack([_random_packing(rng, 512) for _ in range(8)] + [_ids([], 512), _ids([512], 512)]
                    + [_arbitrary_ids(3, 512)])
    n_q, n_k = 512 // blocks[0], 512 // blocks[1]
    host = flash_blocks.needed_blocks(rows, *blocks, causal=causal)
    fwd_steps, bwd_steps = fa._steps(n_q, n_k, *blocks, causal)

    @jax.jit
    def device(seg):
        bounds = flash_blocks.block_bounds(seg, *blocks, xp=jnp)
        return flash_blocks.blocks_needed(bounds, *blocks, causal, xp=jnp), fa._block_map(seg, n_q, n_k, *blocks, causal)

    needed, (forward, backward) = device(jnp.asarray(rows))
    np.testing.assert_array_equal(np.asarray(needed), host)
    for (got, longest), some, steps in ((forward, host, fwd_steps), (backward, host.swapaxes(1, 2), bwd_steps)):
        items, lengths = flash_blocks.work_list(some, steps)
        assert np.asarray(got).dtype == np.int32
        np.testing.assert_array_equal(np.asarray(got).reshape(len(rows), steps), items)
        assert int(longest) == lengths.max()  # the traced grid bound: the batch's longest list
        # for packed ids the lists hold the needed blocks and nothing else
        assert int(lengths.sum()) == int(host.sum())


def test_unsegmented_map_is_the_triangle():
    (forward, fwd_longest), (backward, bwd_longest) = fa._block_map(None, 4, 4, 64, 64, True)
    outer, inner, compute, _, _ = _decoded(forward)
    assert compute.all() and len(forward) == 10 == int(fwd_longest) == int(bwd_longest)
    np.testing.assert_array_equal(np.stack([outer, inner], 1), [(q, k) for q in range(4) for k in range(q + 1)])
    outer, inner, compute, _, _ = _decoded(backward)
    np.testing.assert_array_equal(np.stack([outer, inner], 1), [(k, q) for k in range(4) for q in range(k, 4)])


@pytest.mark.parametrize("surplus", [1, 7])
@pytest.mark.parametrize("causal", [True, False])
def test_surplus_grid_steps_park(monkeypatch, causal, surplus):
    """The body is right under any grid axis no shorter than the list: with
    ``surplus`` more steps than the shape's bound every row parks longer, and
    no bit of o, dq, dk or dv changes."""
    rows = np.stack([ROWS["long_document"], ROWS["short_documents"], ROWS["padded_tail"], ROWS["all_padding"],
                     ROWS["single_document"], ROWS["arbitrary_order"]])
    q, k, v, do = _operands(rows, seed=12, dtype=jnp.bfloat16)

    def run():
        o, vjp = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, segment_ids=jnp.asarray(rows), block_q=_BLOCK, block_k=_BLOCK, interpret=True),
            q, k, v)
        return (o,) + vjp(do)

    fa._block_map.clear_cache()
    exact = run()
    bound = flash_blocks.work_bound
    monkeypatch.setattr(flash_blocks, "work_bound", lambda dense: bound(dense) + surplus)
    fa._block_map.clear_cache()
    try:
        assert fa._steps(4, 4, _BLOCK, _BLOCK, causal)[0] == (10 if causal else 16) + surplus
        longer = run()
    finally:
        monkeypatch.undo()
        fa._block_map.clear_cache()
    for a, b, name in zip(exact, longer, ("o", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32), err_msg=name)


def test_attended_blocks_counts_what_the_kernels_run():
    rows = np.stack([_ids([4097], 4097), _ids([600, 3000, 400], 4097)])[:, :-1]  # the columns the LM attends
    needed, dense, steps = flash_blocks.attended_blocks(rows)
    block_q, block_k = flash_blocks.SEGMENTED_BLOCK_Q, flash_blocks.SEGMENTED_BLOCK_K
    triangle = int(flash_blocks.causal_blocks(4096 // block_q, 4096 // block_k, block_q, block_k).sum())
    assert dense == 2 * triangle
    assert needed == triangle + int(flash_blocks.needed_blocks(rows[1:], block_q, block_k).sum()) < dense
    # the one-document row's list is the triangle, and the other row walks as many steps
    assert steps == 2 * triangle
    # without it every row's list is shorter than the triangle: the grid is the longest of them
    short = np.stack([_ids([600, 3000, 400], 4097), _ids([2000, 2000], 4097)])[:, :-1]
    needed, dense, steps = flash_blocks.attended_blocks(short)
    per_row = flash_blocks.needed_blocks(short, block_q, block_k).sum((1, 2))
    assert needed == per_row.sum() < steps == 2 * per_row.max() < dense
    # a length off the granule is padded as the model pads it
    assert flash_blocks.attended_blocks(np.ones((3, 100), np.int32)) == (3, 3, 3)
    assert flash_blocks.attended_blocks(np.zeros((0, 128), np.int32)) == (0, 0, 0)


@pytest.mark.parametrize("causal", [True, False])
def test_map_is_bit_identical_to_the_dense_grid(causal, patch_rule):
    """bf16 operands, float32 accumulation: skipping blocks changes no bit
    of o, dq, dk or dv against the same kernels made to visit every block of
    the dense grid (a fenced block adds exactly 0)."""
    rows = np.stack([ROWS["long_document"], ROWS["short_documents"], ROWS["padded_tail"], ROWS["all_padding"]])
    q, k, v, do = _operands(rows, seed=8, dtype=jnp.bfloat16)

    def run():
        o, vjp = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, segment_ids=jnp.asarray(rows),
                block_q=_BLOCK, block_k=_BLOCK, interpret=True),
            q, k, v)
        return (o,) + vjp(do)

    mapped = run()

    def every_block(bounds, block_q, block_k, causal=True, xp=np):
        rows, n_q, n_k = len(bounds[0]), bounds[0].shape[1], bounds[2].shape[1]
        grid = flash_blocks.causal_blocks(n_q, n_k, block_q, block_k, xp) if causal else xp.ones((n_q, n_k), bool)
        return xp.broadcast_to(grid[None], (rows, n_q, n_k))

    calls = []

    def counted(*args, **kwargs):
        calls.append(1)
        return every_block(*args, **kwargs)

    patch_rule(counted)
    dense = run()
    assert calls, "the dense rule was never traced: the comparison would be the map against itself"
    for m, d, name in zip(mapped, dense, ("o", "dq", "dk", "dv")):
        np.testing.assert_array_equal(
            np.asarray(m, np.float32), np.asarray(d, np.float32), err_msg=name)


def _starved_of(axis):
    """The rule with block 1 along ``axis`` (1: a q block, 2: a kv block)
    needed by nothing."""
    real = flash_blocks.blocks_needed

    def starved(bounds, block_q, block_k, causal=True, xp=np):
        needed = real(bounds, block_q, block_k, causal, xp)
        keep = xp.arange(needed.shape[axis]) != 1
        return needed & (keep[None, :, None] if axis == 1 else keep[None, None, :])

    return starved


def _starved_run(widths, seed):
    rows = ROWS["short_documents"][None]
    q, k, v, do = _case(_L, _L, *widths, seed=seed)
    # traced here and now: a cached trace (``_flash_fn``) would keep the real rule
    o, vjp = jax.vjp(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, segment_ids=jnp.asarray(rows), block_q=_BLOCK, block_k=_BLOCK, interpret=True),
        q, k, v)
    return (o,) + vjp(do)


@pytest.mark.parametrize("widths", [(32, 32), (192, 128)], ids=["32/32", "192/128"])
def test_q_block_with_no_kv_block_writes_finite_rows(patch_rule, widths):
    """No ids reach this through the rule (a block always needs itself);
    a map that leaves q block 1 nothing must still give finite o and zero
    gradients there, forward and backward skipping alike: the backward
    kernel never visits the block, and its rows of the dq accumulator leave
    as the zeros they were set to."""
    patch_rule(_starved_of(1))
    o, dq, dk, dv = _starved_run(widths, seed=2)
    for t in (o, dq, dk, dv):
        assert np.isfinite(np.asarray(t)).all()
    assert not np.asarray(o)[:, :, _block_rows(1)].any()
    assert not np.asarray(dq)[:, :, _block_rows(1)].any()
    assert np.asarray(dq)[:, :, _block_rows(0)].any() and np.asarray(dq)[:, :, _block_rows(2)].any()


@pytest.mark.parametrize("widths", [(32, 32), (192, 128)], ids=["32/32", "192/128"])
def test_kv_block_that_no_q_block_needs_gets_zero_gradients(patch_rule, widths):
    """The other way round: kv block 1 is needed by no q block, so its one
    item computes nothing and its dk and dv are exactly 0; dq stays finite everywhere."""
    patch_rule(_starved_of(2))
    o, dq, dk, dv = _starved_run(widths, seed=5)
    for t in (o, dq, dk, dv):
        assert np.isfinite(np.asarray(t)).all()
    assert not np.asarray(dk)[:, :, _block_rows(1)].any()
    assert not np.asarray(dv)[:, :, _block_rows(1)].any()
    assert np.asarray(dk)[:, :, _block_rows(0)].any() and np.asarray(dv)[:, :, _block_rows(2)].any()


def test_a_row_too_long_for_the_accumulator_is_refused_by_name():
    """The backward keeps a whole row's dq in VMEM; past what the call may
    ask of the chip it says so at trace time (rows that long are
    ring attention's)."""
    q = jax.ShapeDtypeStruct((1, 1, 82432, 64), jnp.bfloat16)
    loss = lambda q, k, v: flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()  # noqa: E731
    with pytest.raises(ValueError, match="82432 x 64.*ring_attention"):
        jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    fits = jax.ShapeDtypeStruct((1, 1, 81920, 64), jnp.bfloat16)
    assert jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), fits, fits, fits)[0].shape == fits.shape


def test_lists_too_long_for_smem_are_refused_by_name():
    """The work lists of all batch rows ride in SMEM (1 MiB on a v5e): a
    call whose lists would not fit says so at trace time. The longest row
    the backward's VMEM limit allows fits, 19 batch rows of it."""
    loss = lambda q, k, v: flash_attention(q, k, v, causal=False).astype(jnp.float32).sum()  # noqa: E731
    q = jax.ShapeDtypeStruct((1, 1, 262144, 64), jnp.bfloat16)  # 512 x 512 blocks of the square
    with pytest.raises(ValueError, match="SMEM: 1 rows x 262144 blocks"):
        jax.eval_shape(loss, q, q, q)
    ids = jax.ShapeDtypeStruct((20, 81920), jnp.int32)
    packed = lambda q, k, v, seg: flash_attention(q, k, v, causal=True, segment_ids=seg).astype(jnp.float32).sum()  # noqa: E731
    q = jax.ShapeDtypeStruct((20, 1, 81920, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="20 rows x 12880 blocks"):
        jax.eval_shape(packed, q, q, q, ids)
    q, ids = jax.ShapeDtypeStruct((19, 1, 81920, 64), jnp.bfloat16), jax.ShapeDtypeStruct((19, 81920), jnp.int32)
    assert jax.eval_shape(jax.grad(packed, argnums=(0, 1, 2)), q, q, q, ids)[0].shape == q.shape


# -- the scale folded into the exponent, the row statistics lane-wide (PR 37)

#: ((q/k width, v width), the caller's scale or None for 1/sqrt(width)): scales that are no powers of two,
#: 1.0 (``_NEG_BIG · scale · log2 e`` overflows float32: it must only ever meet finite numbers), and value heads
#: of 64, 128 and 256 lanes
_FOLDED = [((128, 128), None), ((192, 128), None), ((64, 64), 1.0), ((64, 256), None), ((24, 64), 0.3)]


_INTERPRETED = functools.partial(flash_attention, block_q=_BLOCK, block_k=_BLOCK, interpret=True)


def _with_scale(fn, causal, seg, scale):
    def run(q, k, v, do):
        o, vjp = jax.vjp(lambda q, k, v: fn(q, k, v, causal=causal, segment_ids=seg, scale=scale), q, k, v)
        return (o,) + vjp(do)

    return jax.jit(run)


@pytest.mark.parametrize("mask", ["segmented_causal", "segmented", "causal"])
@pytest.mark.parametrize("widths,scale", _FOLDED, ids=["{}/{}-scale-{}".format(w[0], w[1], s) for w, s in _FOLDED])
def test_folded_scale_matches_plain(widths, scale, mask):
    """o, dq, dk and dv against the float32 reference where the kernels
    keep the scores in raw units: the probabilities are ``exp2((s - m) · scale
    · log2 e)``, and ``scale`` meets dq and dk once, as they are written."""
    q, k, v, do = _case(_L, _L, *widths, seed=widths[0] + len(mask))
    q = q * (0.35 if scale == 1.0 else 1.0)  # scores of a trained model's size, not of sqrt(width) times it
    seg = jnp.asarray(ROWS["padded_tail"][None]) if mask.startswith("segmented") else None
    causal = mask != "segmented"
    got = _with_scale(_INTERPRETED, causal, seg, scale)(q, k, v, do)
    want = _with_scale(plain_attention, causal, seg, scale)(q, k, v, do)
    for g, w, name in zip(got, want, ("o", "dq", "dk", "dv")):
        assert g.shape == w.shape and np.isfinite(np.asarray(g)).all(), name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-3, rtol=1e-3, err_msg=name)


@pytest.mark.parametrize("widths,scale", _FOLDED, ids=["{}/{}-scale-{}".format(w[0], w[1], s) for w, s in _FOLDED])
def test_kept_lse_is_the_natural_log_sum_exp_of_the_scaled_scores(widths, scale):
    """What a call keeps for its backward (``KEPT_LSE``) is in natural-log
    units of the scaled scores, whatever units the kernel tracks its maximum in."""
    q, k, v, _ = _case(_L, _L, *widths, seed=7)
    q = q * (0.35 if scale == 1.0 else 1.0)
    ids = ROWS["short_documents"]
    used = widths[0] ** -0.5 if scale is None else scale
    merge = lambda t: t.reshape(-1, *t.shape[2:])  # noqa: E731
    _, (_, _, _, _, _, lse) = fa._flash_attention_fwd(
        merge(q), merge(k), merge(v), jnp.asarray(ids[None]), 2, used, True, _BLOCK, _BLOCK, True)
    scores = np.einsum("bhqd,bhkd->bhqk", np.asarray(q, np.float64), np.asarray(k, np.float64)) * used
    seen = (ids[:, None] == ids[None, :]) & (np.arange(_L)[:, None] >= np.arange(_L)[None, :])
    scores = np.where(seen[None, None], scores, -np.inf)
    top = scores.max(-1)
    want = top + np.log(np.exp(scores - top[..., None]).sum(-1))
    np.testing.assert_allclose(np.asarray(lse), want.reshape(-1, _L), rtol=2e-6, atol=2e-5)


@pytest.mark.parametrize("scale", [None, 1.0, 3.0], ids=["default", "scale-1", "scale-3"])
@pytest.mark.parametrize("starved", [1, 2], ids=["q_block", "kv_block"])
def test_rows_that_see_nothing_give_zeros_at_any_scale(patch_rule, starved, scale):
    """The running maximum starts above the masked scores (``_M_NONE``), so
    a query row that is served no key, because its q block gets no kv block
    or because the one kv block that held its document is withheld, ends with
    an output of exactly 0 and no gradient, and puts nothing into dk or dv.
    At scale 1 ``_NEG_BIG · scale · log2 e`` is past float32 and at 3 so is
    ``_M_NONE · scale``: nothing may come to ``inf - inf``."""
    patch_rule(_starved_of(starved))
    rows = ROWS["short_documents"]
    q, k, v, do = _case(_L, _L, 32, 32, seed=9)
    q = q * (1.0 if scale is None else 0.35 / scale)
    run = _with_scale(_INTERPRETED, True, jnp.asarray(rows[None]), scale)  # traced below, under the patched rule
    got = run(q, k, v, do)
    for t, name in zip(got, ("o", "dq", "dk", "dv")):
        assert np.isfinite(np.asarray(t)).all(), name
    block, blind = _block_rows(1), np.zeros(_L, bool)
    # all of q block 1, or its rows whose document starts inside it: causal, so every key of theirs was in the withheld block
    blind[block] = True if starved == 1 else rows[block] != rows[block.start - 1]
    assert blind.any() and not blind.all()
    o, dq = np.asarray(got[0]), np.asarray(got[1])
    assert not o[:, :, blind].any() and not dq[:, :, blind].any()
    assert o[:, :, ~blind].any() and dq[:, :, ~blind].any()
    # and the rows that do see keys are what they are without the blind rows' cotangent
    again = run(q, k, v, do * jnp.asarray(~blind, do.dtype)[None, None, :, None])
    for g, w, name in zip(got, again, ("o", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)
