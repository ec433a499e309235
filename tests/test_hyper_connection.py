"""The hyper-connections' four passes (``ops/hyper_connection.py``, kernels
interpreted) through ``models/decoder.HyperConnection``: values and every
gradient against the benchmark's plain reference
(``benchmarks/reference/moe_lm.hyper_connected``) under ``jax.grad``, where
the trace's readers will find the backward pass, and the batch sharding on a
mesh of several devices."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from decoder_testutil import REF, packed_batch, params, program_config, program_loss, reference, tree_close  # noqa: F401
from tensorflowonspark_tpu.models import decoder
from tensorflowonspark_tpu.ops import hyper_connection
from tensorflowonspark_tpu.train import SyncDataParallel

# float32 streams: the kernels add in another order than the reference (a chunk
# of lanes at a time, the tiles of ``d phi`` one after another): the decoder
# tests' tolerances for values (2e-4) and gradients (5e-4) of float32 sums.
# bfloat16 streams: the reference is given the same rounded streams and
# cotangent in float32, and the program rounds ``phi``, ``h``, ``y``, ``X'``
# and the cotangents it hands back to 8 bits (2^-8 = 0.4% each); a gradient
# passes three or four such roundings, read 1.2% at worst: 3e-2. With two
# streams Sinkhorn's map has one degree of freedom and ``b_res``'s gradient is
# a sum over the tokens that all but cancels: 7.8% there, and the same 7.8% from
# the stream-by-stream code this path replaced: 1e-1 for that case.
TOLERANCE = {"float32": (2e-4, 5e-4), "bfloat16": (3e-2, 3e-2), "bfloat16, two streams": (3e-2, 1e-1)}


def _weights(n, d, seed):
    """One sub-layer's maps, seeded, with dynamic terms large enough to
    matter (the model's alpha 0.01 would hide ``phi``'s gradients)."""
    key = jax.random.PRNGKey(seed)
    normal = lambda i, shape, scale: scale * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)  # noqa: E731
    std = 3.0 * (n * d) ** -0.5
    return {
        "phi_pre": normal(0, (n, d, n), std), "phi_post": normal(1, (n, d, n), std), "phi_res": normal(2, (n, d, n * n), std),
        "alpha_pre": jnp.float32(0.7), "alpha_post": jnp.float32(0.5), "alpha_res": jnp.float32(0.9),
        "b_pre": normal(3, (n,), 0.3), "b_post": normal(4, (n,), 0.3), "b_res": 2.0 * jnp.eye(n) + normal(5, (n, n), 0.3),
    }


@pytest.mark.parametrize("dtype,n,d,rows,seq", [
    ("float32", 4, 32, 2, 48),  # the decoder tests' shape: one tile, one chunk of lanes
    ("float32", 2, 384, 2, 200),  # 400 tokens: five tiles of 80, d phi summed across them; three chunks of 128 lanes
    ("float32", 4, 160, 3, 40),  # 120 tokens in chunks of 8 rows; 160 lanes are no multiple of a chunk: taken whole
    ("bfloat16", 4, 128, 2, 64),
    ("bfloat16", 2, 48, 3, 40),
])
def test_forward_and_every_gradient_match_the_reference(dtype, n, d, rows, seq):
    ref = dict(REF, hc_mult=n, hidden_size=d)
    cfg = decoder.DecoderConfig.from_dict(program_config(ref, dtype=dtype))
    p = _weights(n, d, seed=11)
    key = jax.random.PRNGKey(12)
    streams = jax.random.normal(key, (rows, seq, n, d), jnp.float32).astype(dtype)
    weigh = jax.random.normal(jax.random.fold_in(key, 1), streams.shape, jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(key, 2), (d, d), jnp.float32) * d ** -0.5
    assert hyper_connection._token_tile(rows * seq, 1) == {96: 96, 400: 80, 120: 120, 128: 128}[rows * seq]

    def sublayer(h):
        return (3.0 * jnp.tanh(h.astype(jnp.float32) @ w)).astype(h.dtype)

    def program(p, streams, y_weight):
        h, maps = decoder.HyperConnection(cfg).apply({"params": p}, streams)
        return decoder.HyperConnection.merge(streams, maps, y_weight * sublayer(h))

    def plain(p, streams, y_weight):
        return reference.hyper_connected(streams.astype(jnp.float32), p, lambda h: y_weight * sublayer(h), ref)

    def both(fn):  # y's gradient through a factor on it: every stream-sized and every small input is covered
        def loss(p, streams, y_weight):
            return jnp.sum(fn(p, streams, y_weight).astype(jnp.float32) * weigh.astype(jnp.float32))

        return jax.jit(lambda *a: (fn(*a), jax.grad(loss, argnums=(0, 1, 2))(*a)))(p, streams, jnp.ones((), dtype))

    (out, grads), (want_out, want) = both(program), both(plain)
    assert out.shape == streams.shape and out.dtype == streams.dtype
    assert grads[1].dtype == streams.dtype and all(g.dtype == jnp.float32 for g in jax.tree.leaves(grads[0]))
    value_tol, grad_tol = TOLERANCE[dtype + (", two streams" if n == 2 and dtype == "bfloat16" else "")]
    tree_close(out.astype(jnp.float32), want_out, value_tol)
    tree_close(jax.tree.map(lambda g: g.astype(jnp.float32), grads), want, grad_tol)


def test_carried_flat_the_streams_give_what_the_four_dimensional_give():
    """``Decoder`` carries ``vec(X)``, ``[B, L, n * d]``; the module answers
    ``[B, L, n, d]`` in kind, with the same numbers."""
    cfg = decoder.DecoderConfig.from_dict(program_config(REF))
    n, d = REF["hc_mult"], REF["hidden_size"]
    p = _weights(n, d, seed=3)
    streams = jax.random.normal(jax.random.PRNGKey(4), (2, 24, n, d), jnp.float32)

    def program(streams):
        h, maps = decoder.HyperConnection(cfg).apply({"params": p}, streams)
        return decoder.HyperConnection.merge(streams, maps, jnp.tanh(h))

    flat = program(streams.reshape(2, 24, n * d))
    assert flat.shape == (2, 24, n * d)
    np.testing.assert_array_equal(np.asarray(flat).reshape(streams.shape), np.asarray(program(streams)))


def test_on_a_chip_unaligned_streams_are_refused_by_name():
    x = jnp.zeros((32, 4 * 96), jnp.bfloat16)
    with pytest.raises(ValueError, match="128 lanes"):
        hyper_connection._sizes(x, 4, interpret=False)
    assert hyper_connection._sizes(x, 4, interpret=True) == (32, 384, 96, 2)
    with pytest.raises(ValueError, match="multiple of 16"):
        hyper_connection._token_tile(8200, 1 << 20)


def _op_names(compiled_text):
    return re.findall(r'op_name="([^"]*)"', compiled_text)


def test_the_step_books_the_backward_pass_under_the_scope_and_the_phase(params):  # noqa: F811
    """What ``moe_mhc_time_pct`` and ``moe_bwd_pct`` read: an operation's
    ``op_name`` holds ``/tos.mhc/`` for both rules of both passes, and
    ``transpose(jvp(`` in the backward pass (``rematted_computation`` where
    the layer is recomputed)."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks.layer_metrics import _moe, _program

    _, loss_fn = program_loss(remat=True)
    strategy = SyncDataParallel(mesh=None)
    optimizer = optax.adamw(1e-3)
    state = strategy.create_state(lambda: {"params": params}, optimizer)
    step = strategy.compile_train_step(loss_fn, optimizer, has_aux=True)
    batch = strategy.shard_batch({k: np.asarray(v) for k, v in packed_batch(rows=8).items()})
    # (an operation the CPU compiler merged out of several carries all their names, ";" between: left out)
    names = [n for n in _op_names(step.lower(state, batch).compile().as_text())
             if _moe.in_scope(n, "tos.mhc") and ";" not in n]
    phases = {}
    for name in names:
        for kernel in ("mhc_read_bwd", "mhc_merge_bwd", "mhc_read", "mhc_merge"):
            if "/" + kernel + "/" in name:
                phases.setdefault(kernel, set()).add(_program.phase_of(name))
                break
    assert phases["mhc_read_bwd"] == {"bwd"} and phases["mhc_merge_bwd"] == {"bwd"}
    assert all("transpose(jvp(" in n for n in names if "_bwd/" in n)
    assert phases["mhc_read"] == {"fwd", "recompute"} and phases["mhc_merge"] == {"fwd", "recompute"}
    # what stays XLA's (the maps from z, Sinkhorn's rounds) is booked in all three phases too
    assert {"fwd", "recompute", "bwd"} <= {_program.phase_of(n) for n in names if "/mhc_" not in n}


def test_streams_keep_the_batch_sharding_through_both_passes(params):  # noqa: F811
    """On a mesh the kernels run per shard of the batch (``shard_map``): the
    streams leave a layer sharded as ``Decoder._constrain`` left them, the
    loss and every gradient equal the one-device program's, the maps'
    parameters' gradients summed over the shards."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg = decoder.DecoderConfig.from_dict(program_config(REF))
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    n, d = REF["hc_mult"], REF["hidden_size"]
    batch = packed_batch(rows=4)
    seg, pos = batch["segment_ids"][:, :-1], batch["positions"][:, :-1]
    streams = jax.random.normal(jax.random.PRNGKey(5), (4, seg.shape[1], n * d), jnp.float32)
    weigh = jax.random.normal(jax.random.PRNGKey(6), streams.shape)
    p = params["layer_1"]

    def layer(mesh):
        def run(p, streams):
            out, _, _ = decoder.DecoderLayer(cfg, cfg.plan[1], mesh).apply({"params": p}, streams, pos, seg)
            return out

        return run

    def both(mesh):
        fn = layer(mesh)
        return jax.jit(lambda p, s: (fn(p, s), jax.grad(lambda p, s: jnp.sum(fn(p, s) * weigh), argnums=(0, 1))(p, s)))

    rows = NamedSharding(mesh, P("dp", None, None))
    with mesh:
        out, grads = both(mesh)(p, jax.device_put(streams, rows))
    assert out.sharding.is_equivalent_to(rows, out.ndim)
    assert grads[1].sharding.is_equivalent_to(rows, out.ndim)
    want_out, want = both(None)(p, streams)
    tree_close(out, want_out)
    tree_close(grads, want, 5e-4)
