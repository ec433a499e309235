"""``ops/ssd_scan``: the two kernels, interpreted, against the recurrence a
position at a time (``lax.scan``) — outputs and every gradient, with restarts
inside a chunk, at a chunk's edge and none, float32 and bfloat16 operands,
chunk lengths that do and do not divide the row — and what the call refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.ops import ssd_scan as ssd

ROWS, LENGTH, HEADS, GROUPS, WIDTH, STATES, CHUNK = 2, 48, 4, 2, 8, 16, 16

#: segment ids of the two rows: where the documents start, against chunks of 16
RESTARTS = {
    "inside a chunk": [[1] * 10 + [2] * 13 + [3] * 20 + [0] * 5, [1] * 5 + [2] * 43],
    "at a chunk's edge": [[1] * 16 + [2] * 16 + [3] * 16, [1] * 32 + [0] * 16],
    "none": None,
}


def plain_scan(x, delta, a, b, c, skip, ids):
    """The module's recurrence, a position at a time, float32."""
    x, delta, b, c = (t.astype(jnp.float32) for t in (x, delta, b, c))
    ids = jnp.ones(x.shape[:2], jnp.int32) if ids is None else ids
    first = jnp.concatenate([jnp.ones((ids.shape[0], 1), bool), ids[:, 1:] != ids[:, :-1]], axis=1)
    per = x.shape[2] // b.shape[2]
    b, c = jnp.repeat(b, per, axis=2), jnp.repeat(c, per, axis=2)  # [rows, L, H, N]

    def row(x, delta, b, c, first):
        def step(h, now):
            xx, d, bb, cc, start = now
            h = jnp.where(start, 0.0, jnp.exp(d * a)[:, None, None] * h) + (d[:, None] * xx)[..., None] * bb[:, None, :]
            return h, jnp.einsum("hpn,hn->hp", h, cc) + skip[:, None] * xx

        return jax.lax.scan(step, jnp.zeros(x.shape[1:] + (b.shape[-1],), jnp.float32), (x, delta, b, c, first))[1]

    return jax.vmap(row)(x, delta, b, c, first)


def operands(dtype, length=LENGTH, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(keys[0], (ROWS, length, HEADS, WIDTH)).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(keys[1], (ROWS, length, HEADS)) - 1.0)
    a = -jnp.exp(0.5 * jax.random.normal(keys[2], (HEADS,)))
    b = jax.random.normal(keys[3], (ROWS, length, GROUPS, STATES)).astype(dtype)
    c = jax.random.normal(keys[4], (ROWS, length, GROUPS, STATES)).astype(dtype)
    skip = jax.random.normal(keys[5], (HEADS,))
    weights = jax.random.normal(keys[6], (ROWS, length, HEADS, WIDTH))
    return (x, delta, a, b, c, skip), weights


def _close(got, want, tolerance, name=""):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    assert got.shape == want.shape, name
    assert float(jnp.linalg.norm(got - want)) <= tolerance * float(jnp.linalg.norm(want)), name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("restarts", list(RESTARTS))
def test_kernels_match_the_recurrence(restarts, dtype):
    ids = None if RESTARTS[restarts] is None else jnp.asarray(RESTARTS[restarts], jnp.int32)
    args, weights = operands(dtype)
    run = lambda *a: ssd.ssd_scan(*a, ids, chunk=CHUNK, interpret=True)  # noqa: E731
    got, want = run(*args), plain_scan(*args, ids)
    assert got.dtype == dtype
    # the products take their operands in x's dtype and y is rounded to it: a bfloat16 run differs by those roundings
    _close(got, want, 1e-5 if dtype == jnp.float32 else 2 ** -6)
    grads = jax.grad(lambda *a: jnp.sum(run(*a).astype(jnp.float32) * weights), argnums=range(6))(*args)
    wanted = jax.grad(lambda *a: jnp.sum(plain_scan(*a, ids) * weights), argnums=range(6))(*args)
    for name, g, w in zip(("x", "delta", "a", "b", "c", "skip"), grads, wanted):
        assert g.dtype == w.dtype, name
        _close(g, w, 2e-5 if dtype == jnp.float32 else 2 ** -5, name)


def test_a_document_starts_from_zero_whatever_shares_its_row():
    """The second document of a packed row reads what it reads alone."""
    args, _ = operands(jnp.float32)
    ids = jnp.asarray(RESTARTS["inside a chunk"], jnp.int32)
    packed = ssd.ssd_scan(*args, ids, chunk=CHUNK, interpret=True)
    alone = ssd.ssd_scan(*(t[:1, 10:23] if t.ndim > 1 else t for t in args), None, chunk=CHUNK, interpret=True)
    np.testing.assert_allclose(packed[0, 10:23], alone[0], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("length,chunk", [(37, 16), (48, 32), (5, 16), (48, 128)],
                         ids=["37 in chunks of 16", "48 in chunks of 32", "a row shorter than eight", "one chunk"])
def test_chunks_that_do_not_divide_the_row(length, chunk):
    args, weights = operands(jnp.float32, length=length, seed=3)
    ids = jnp.asarray([[1] * 3 + [2] * (length - 3), [1] * length], jnp.int32)
    run = lambda *a: ssd.ssd_scan(*a, ids, chunk=chunk, interpret=True)  # noqa: E731
    _close(run(*args), plain_scan(*args, ids), 2e-5)
    grads = jax.grad(lambda *a: jnp.sum(run(*a) * weights), argnums=range(6))(*args)
    wanted = jax.grad(lambda *a: jnp.sum(plain_scan(*a, ids) * weights), argnums=range(6))(*args)
    for g, w in zip(grads, wanted):
        _close(g, w, 2e-5)


def test_strong_decays_stay_finite():
    """A running sum of -6000 within a chunk: the masked entries never reach ``exp``."""
    (x, delta, a, b, c, skip), _ = operands(jnp.float32)
    run = lambda delta: ssd.ssd_scan(x, delta, 16.0 * a, b, c, skip, None, chunk=CHUNK, interpret=True)  # noqa: E731
    y, grad = jax.value_and_grad(lambda d: jnp.sum(run(d)))(50.0 * delta)
    assert bool(jnp.isfinite(y)) and bool(jnp.all(jnp.isfinite(grad)))


def test_chunks_of():
    assert ssd.chunks_of(8192) == (128, 64) and ssd.chunks_of(37, 16) == (16, 3) and ssd.chunks_of(5) == (8, 1)


def test_refused_shapes():
    (x, delta, a, b, c, skip), _ = operands(jnp.float32)
    with pytest.raises(ValueError, match="ssd scan"):
        ssd.ssd_scan(x, delta, a, b[:, :, :1].repeat(3, axis=2), c[:, :, :1].repeat(3, axis=2), skip, interpret=True)
    with pytest.raises(ValueError, match="multiples of 128"):
        ssd.ssd_scan(x, delta, a, b, c, skip)  # on a chip a group's lanes are whole registers
