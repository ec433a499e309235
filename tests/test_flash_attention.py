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

    def walked(needed, axis):
        # a kernel walks from the first needed block to the last: for ids in
        # no order that range may hold a block between two needed ones
        ahead = np.maximum.accumulate(needed, axis)
        behind = np.flip(np.maximum.accumulate(np.flip(needed, axis), axis), axis)
        return ahead & behind

    forward = walked(needed, 1)  # q-major: per q block a range of kv blocks
    backward = walked(needed, 0)  # kv-major, dq as well: per kv block a range of q blocks
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
        skipped = [ik for ik in range(n) if not forward[iq, ik]]
        o_p = run(q, poisoned(k, skipped), poisoned(v, skipped), seg, do)[0]
        np.testing.assert_array_equal(np.asarray(o_p)[:, :, _block_rows(iq)], o[:, :, _block_rows(iq)])
        # dq of this q block reads its own o and lse too: poison what neither pass walks
        skipped = [ik for ik in range(n) if not (forward[iq, ik] or backward[iq, ik])]
        dq_p = run(q, poisoned(k, skipped), poisoned(v, skipped), seg, do)[1]
        np.testing.assert_array_equal(np.asarray(dq_p)[:, :, _block_rows(iq)], dq[:, :, _block_rows(iq)])
    for ik in range(n):
        skipped = [iq for iq in range(n) if not backward[iq, ik]]
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


@pytest.mark.parametrize("blocks", [(64, 64), (64, 32)])
def test_device_map_is_the_host_rule(blocks):
    """The jitted step's map and the host's counters state one rule: equal
    block for block, and for packed ids the walked ranges hold exactly the
    needed blocks."""
    rng = np.random.default_rng(9)
    rows = np.stack([_random_packing(rng, 512) for _ in range(8)] + [_ids([], 512), _ids([512], 512)])
    n_q, n_k = 512 // blocks[0], 512 // blocks[1]
    host = flash_blocks.needed_blocks(rows, *blocks)

    @jax.jit
    def device(seg):
        bounds = flash_blocks.block_bounds(seg, *blocks, xp=jnp)
        return flash_blocks.blocks_needed(bounds, *blocks, xp=jnp), fa._block_map(seg, n_q, n_k, *blocks, True)

    needed, ((kv_lo, kv_hi), (q_lo, q_hi)) = device(jnp.asarray(rows))
    np.testing.assert_array_equal(np.asarray(needed), host)
    assert int((np.asarray(kv_hi) - np.asarray(kv_lo) + 1).sum()) == int(host.sum())
    assert int((np.asarray(q_hi) - np.asarray(q_lo) + 1).sum()) == int(host.sum())
    np.testing.assert_array_equal(np.asarray(kv_lo).reshape(len(rows), n_q), host.argmax(2))
    np.testing.assert_array_equal(np.asarray(q_lo).reshape(len(rows), n_k), host.argmax(1))


def test_attended_blocks_counts_what_the_kernels_run():
    rows = np.stack([_ids([4097], 4097), _ids([600, 3000, 400], 4097)])[:, :-1]  # the columns the LM attends
    needed, dense = flash_blocks.attended_blocks(rows)
    block_q, block_k = flash_blocks.SEGMENTED_BLOCK_Q, flash_blocks.SEGMENTED_BLOCK_K
    triangle = int(flash_blocks.causal_blocks(4096 // block_q, 4096 // block_k, block_q, block_k).sum())
    assert dense == 2 * triangle
    assert needed == triangle + int(flash_blocks.needed_blocks(rows[1:], block_q, block_k).sum()) < dense
    # a length off the granule is padded as the model pads it
    assert flash_blocks.attended_blocks(np.ones((3, 100), np.int32)) == (3, 3)


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
    """The other way round: kv block 1 is needed by no q block, so its walk
    is empty and its dk and dv are exactly 0; dq stays finite everywhere."""
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
