"""``ops/selective_scan``: the two kernels, interpreted, against a plain
``lax.scan`` over positions — outputs and every gradient, with restarts
inside a chunk, at a chunk's edge and none, float32 and bfloat16 operands, a
row that is no multiple of its chunk, channel blocks of more than one
register's lanes — and what the call refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.ops import selective_scan as ss

ROWS, LENGTH, CHANNELS, STATES, CHUNK = 2, 48, 256, 16, 16

#: segment ids of the two rows: where the documents start, against chunks of 16
RESTARTS = {
    "inside a chunk": [[1] * 10 + [2] * 13 + [3] * 20 + [0] * 5, [1] * 5 + [2] * 43],
    "at a chunk's edge": [[1] * 16 + [2] * 16 + [3] * 16, [1] * 32 + [0] * 16],
    "none": None,
}


def plain_scan(dt, x, b, c, a, skip, ids):
    """The module's recurrence, a position at a time, float32."""
    dt, x, b, c = (t.astype(jnp.float32) for t in (dt, x, b, c))
    delta = jax.nn.softplus(dt)
    ids = jnp.ones(x.shape[:2], jnp.int32) if ids is None else ids
    first = jnp.concatenate([jnp.ones((ids.shape[0], 1), bool), ids[:, 1:] != ids[:, :-1]], axis=1)

    def row(delta, x, b, c, first):
        def step(h, now):
            d, xx, bb, cc, start = now
            h = jnp.where(start, 0.0, jnp.exp(d[:, None] * a) * h) + (d * xx)[:, None] * bb[None, :]
            return h, h @ cc + skip * xx

        return jax.lax.scan(step, jnp.zeros(a.shape, jnp.float32), (delta, x, b, c, first))[1]

    return jax.vmap(row)(delta, x, b, c, first)


def operands(dtype, length=LENGTH, channels=CHANNELS, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    dt = jax.random.normal(keys[0], (ROWS, length, channels)).astype(dtype)
    x = jax.random.normal(keys[1], (ROWS, length, channels)).astype(dtype)
    b = jax.random.normal(keys[2], (ROWS, length, STATES)).astype(dtype)
    c = jax.random.normal(keys[3], (ROWS, length, STATES)).astype(dtype)
    a = -jnp.exp(0.5 * jax.random.normal(keys[4], (channels, STATES)))
    skip = jax.random.normal(keys[5], (channels,))
    weights = jax.random.normal(keys[6], (ROWS, length, channels))
    return (dt, x, b, c, a, skip), weights


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("restarts", list(RESTARTS))
def test_kernels_match_a_plain_scan(restarts, dtype):
    ids = None if RESTARTS[restarts] is None else jnp.asarray(RESTARTS[restarts], jnp.int32)
    args, weights = operands(dtype)
    run = lambda *a: ss.selective_scan(*a, ids, chunk=CHUNK, block_d=128, block_d_bwd=128, interpret=True)  # noqa: E731
    got, want = run(*args), plain_scan(*args, ids)
    assert got.dtype == dtype and got.shape == want.shape
    # y is rounded to the operands' dtype as it is written: a bfloat16's half unit in the last place
    tolerance = 1e-5 if dtype == jnp.float32 else 2 ** -8
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) <= tolerance * scale
    grads = jax.grad(lambda *a: jnp.sum(run(*a).astype(jnp.float32) * weights), argnums=range(6))(*args)
    wanted = jax.grad(lambda *a: jnp.sum(plain_scan(*a, ids) * weights), argnums=range(6))(*args)
    for name, g, w in zip(("dt", "x", "b", "c", "a", "skip"), grads, wanted):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        apart = float(jnp.linalg.norm(g.astype(jnp.float32) - w.astype(jnp.float32)))
        # float32 state inside whatever the operands: a bfloat16 run differs by the rounding of its results alone
        assert apart <= (2e-5 if dtype == jnp.float32 else 2 ** -7) * float(jnp.linalg.norm(w.astype(jnp.float32))), name


def test_a_document_starts_from_zero_whatever_shares_its_row():
    """The second document of a packed row reads what it reads alone."""
    args, _ = operands(jnp.float32)
    ids = jnp.asarray(RESTARTS["inside a chunk"], jnp.int32)
    run = lambda a, ids: ss.selective_scan(*a, ids, chunk=CHUNK, block_d=128, block_d_bwd=128, interpret=True)  # noqa: E731
    packed = run(args, ids)
    alone = run(tuple(t[:1, 10:23] if t.ndim == 3 else t for t in args), None)  # row 0's second document
    np.testing.assert_allclose(packed[0, 10:23], alone[0], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("length,block", [(37, 128), (48, 256)], ids=["a row no multiple of eight", "two registers a block"])
def test_padding_and_wide_blocks(length, block):
    args, weights = operands(jnp.float32, length=length, seed=3)
    ids = jnp.asarray([[1] * 9 + [2] * (length - 9), [1] * length], jnp.int32)
    run = lambda *a: ss.selective_scan(*a, ids, chunk=CHUNK, block_d=block, block_d_bwd=block, interpret=True)  # noqa: E731
    np.testing.assert_allclose(run(*args), plain_scan(*args, ids), rtol=2e-5, atol=2e-5)
    grads = jax.grad(lambda *a: jnp.sum(run(*a) * weights), argnums=range(6))(*args)
    wanted = jax.grad(lambda *a: jnp.sum(plain_scan(*a, ids) * weights), argnums=range(6))(*args)
    for g, w in zip(grads, wanted):
        assert float(jnp.linalg.norm(g - w)) <= 2e-5 * float(jnp.linalg.norm(w))


def test_restarts_are_the_documents_first_positions():
    ids = jnp.asarray([[1, 1, 2, 2, 2, 0, 0], [5, 5, 5, 5, 5, 5, 5]], jnp.int32)
    assert ss.restarts(ids).tolist() == [[True, False, True, False, False, True, False], [True] + [False] * 6]


@pytest.mark.parametrize("channels,states", [(100, 16), (128, 12)], ids=["channels off the lanes", "states off the sublanes"])
def test_refused_shapes(channels, states):
    z = jnp.zeros((1, 8, channels))
    with pytest.raises(ValueError, match="selective scan"):
        ss.selective_scan(z, z, jnp.zeros((1, 8, states)), jnp.zeros((1, 8, states)), -jnp.ones((channels, states)),
                          jnp.ones((channels,)), interpret=True)
