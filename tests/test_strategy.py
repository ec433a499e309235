"""train/strategy tests: sync DP and FSDP training on the 8-device CPU mesh."""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from tensorflowonspark_tpu import parallel
from tensorflowonspark_tpu.parallel.sharding import _spec_axes
from tensorflowonspark_tpu.train import SyncDataParallel, TrainState, steps_per_worker


def _linear_init(rng):
    k1, k2 = jax.random.split(rng)
    return {
        "w": jax.random.normal(k1, (2, 1)) * 0.01,
        "b": jnp.zeros((1,)),
    }


def _linear_loss(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def _make_data(n=256, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 2)).astype(np.float32)
    y = x @ np.array([[3.14], [1.618]], np.float32) + 0.5
    return {"x": x, "y": y}


@pytest.mark.parametrize("axes,fsdp", [({"dp": 8}, False), ({"dp": 2, "fsdp": 4}, True)])
def test_training_converges(axes, fsdp):
    mesh = parallel.build_mesh(axes)
    strategy = SyncDataParallel(mesh, fsdp=fsdp)
    optimizer = optax.sgd(0.1)
    state = strategy.create_state(_linear_init, optimizer, jax.random.PRNGKey(0))
    step = strategy.compile_train_step(_linear_loss, optimizer)
    batch = strategy.shard_batch(_make_data())
    for _ in range(150):
        state, metrics = step(state, batch)
        # the virtual-device CPU backend aborts on collective rendezvous
        # timeouts if the async dispatch queue gets deep — block every step
        # (harmless on CPU; real TPU loops want the async pipeline)
        jax.block_until_ready(metrics["loss"])
    assert float(metrics["loss"]) < 1e-3
    assert int(metrics["step"]) == 150
    w = np.asarray(jax.device_get(state.params["w"]))
    np.testing.assert_allclose(w.ravel(), [3.14, 1.618], atol=0.05)


def test_fsdp_params_actually_sharded():
    mesh = parallel.build_mesh({"fsdp": 8})
    strategy = SyncDataParallel(mesh, fsdp=True, min_weight_size=8)

    def init(rng):
        return {"big": jax.random.normal(rng, (64, 16)), "bias": jnp.zeros((3,))}

    optimizer = optax.adam(1e-3)
    state = strategy.create_state(init, optimizer, jax.random.PRNGKey(0))
    assert state.params["big"].sharding.spec == P("fsdp", None)
    assert state.params["bias"].sharding.spec == P()
    # adam moments mirror the param shardings
    mu = state.opt_state[0].mu
    assert mu["big"].sharding.spec == P("fsdp", None)


def test_train_step_with_aux_metrics():
    mesh = parallel.build_mesh({"dp": 8})
    strategy = SyncDataParallel(mesh)

    def loss_with_acc(params, batch):
        pred = batch["x"] @ params["w"] + params["b"]
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, {"mae": jnp.mean(jnp.abs(pred - batch["y"]))}

    optimizer = optax.sgd(0.05)
    state = strategy.create_state(_linear_init, optimizer, jax.random.PRNGKey(1))
    step = strategy.compile_train_step(loss_with_acc, optimizer, has_aux=True)
    state, metrics = step(state, strategy.shard_batch(_make_data()))
    assert set(metrics) == {"loss", "step", "mae"}


def test_predict_step_outputs_replicated():
    mesh = parallel.build_mesh({"dp": 8})
    strategy = SyncDataParallel(mesh)
    optimizer = optax.sgd(0.1)
    state = strategy.create_state(_linear_init, optimizer, jax.random.PRNGKey(0))
    predict = strategy.compile_predict_step(
        lambda params, batch: batch["x"] @ params["w"] + params["b"]
    )
    batch = strategy.shard_batch(_make_data(n=32))
    out = predict(state.params, batch)
    assert out.shape == (32, 1)
    assert out.sharding.is_fully_replicated


def test_state_checkpoint_roundtrip(tmp_path):
    from tensorflowonspark_tpu.train import checkpoint

    mesh = parallel.build_mesh({"dp": 8})
    strategy = SyncDataParallel(mesh)
    optimizer = optax.sgd(0.1)
    state = strategy.create_state(_linear_init, optimizer, jax.random.PRNGKey(0))
    step = strategy.compile_train_step(_linear_loss, optimizer)
    state, _ = step(state, strategy.shard_batch(_make_data()))

    path = checkpoint.save_checkpoint(str(tmp_path / "ckpt_1"), state)
    restored = checkpoint.restore_checkpoint(path, target=jax.device_get(state))
    np.testing.assert_allclose(
        np.asarray(restored.params["w"]), np.asarray(jax.device_get(state.params["w"]))
    )
    assert checkpoint.latest_checkpoint(str(tmp_path)) == path


def test_steps_per_worker():
    # 60000 MNIST examples, batch 64, 3 workers -> int(312 * 0.9) = 280
    assert steps_per_worker(60000, 64, 3) == 280
    assert steps_per_worker(10, 64, 3) == 1  # never zero


def test_compile_train_loop_matches_sequential_steps():
    """K scanned steps inside one jit == K sequential step() calls."""
    import numpy as np
    import optax

    from tensorflowonspark_tpu import parallel
    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.train import SyncDataParallel

    mesh = parallel.build_mesh({"dp": 8})
    strategy = SyncDataParallel(mesh)
    model = mnist.create_model("mlp", hidden=16)
    opt = optax.sgd(0.1)
    rng = np.random.default_rng(0)
    K = 4
    host_batches = [
        {
            "image": rng.standard_normal((16, 28, 28)).astype(np.float32),
            "label": rng.integers(0, 10, 16),
        }
        for _ in range(K)
    ]

    state_a = strategy.create_state(mnist.make_init_fn(model), opt, jax.random.PRNGKey(0))
    loop = strategy.compile_train_loop(mnist.make_loss_fn(model), opt, K, has_aux=True, donate=False)
    device_batches = [strategy.shard_batch(b) for b in host_batches]
    state_a, metrics = loop(state_a, device_batches)
    jax.block_until_ready(metrics["loss"])
    # batch-count mismatch is a loud error, not a silent shorter run
    import pytest as _pytest

    with _pytest.raises(ValueError, match="batches"):
        loop(state_a, device_batches[:2])

    state_b = strategy.create_state(mnist.make_init_fn(model), opt, jax.random.PRNGKey(0))
    step = strategy.compile_train_step(mnist.make_loss_fn(model), opt, has_aux=True, donate=False)
    for batch in host_batches:
        state_b, m = step(state_b, strategy.shard_batch(batch))
        jax.block_until_ready(m["loss"])

    np.testing.assert_allclose(float(metrics["loss"]), float(m["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(state_a.params), jax.tree.leaves(state_b.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_loop_prefetch_windows_and_drops_remainder():
    from tensorflowonspark_tpu.data import loop_prefetch

    mesh = parallel.build_mesh({"dp": 8})
    strategy = SyncDataParallel(mesh)
    rng = np.random.default_rng(0)
    host = [{"x": rng.standard_normal((8, 2)).astype(np.float32)} for _ in range(10)]
    windows = list(loop_prefetch(iter(host), strategy, num_steps=4))
    # 10 batches -> two full windows of 4; the short remainder is dropped
    assert [len(w) for w in windows] == [4, 4]
    flat = [b for w in windows for b in w]
    for got, want in zip(flat, host[:8]):
        np.testing.assert_allclose(np.asarray(got["x"]), want["x"])


@pytest.mark.parametrize("depth", [None, 1, 3], ids=["depth-default", "depth-1", "depth-3"])
@pytest.mark.parametrize("num_steps", [0, 2, 4], ids=["device_prefetch", "loop_prefetch-2", "loop_prefetch-4"])
@pytest.mark.parametrize("n", [1, 7, 11, 16])
def test_device_feeds_deliver_the_source_in_order(n, num_steps, depth):
    """The stream is the source's, in order, short tails dropped: what
    ``device_prefetch`` (``num_steps`` 0 here) and ``loop_prefetch`` hand out,
    concatenated, is the source up to the last whole window, whatever the
    depth they place ahead."""
    from tensorflowonspark_tpu.data import device_prefetch, loop_prefetch

    strategy = SyncDataParallel(parallel.build_mesh({"dp": 8}))
    rng = np.random.default_rng(n)
    host = [{"x": rng.standard_normal((8, 2)).astype(np.float32), "i": np.full((8,), i, np.int32)} for i in range(n)]
    kw = {} if depth is None else {"depth": depth}
    if num_steps:
        windows = list(loop_prefetch(iter(host), strategy, num_steps=num_steps, **kw))
        assert all(len(w) == num_steps for w in windows)
        got = [b for w in windows for b in w]
    else:
        got = list(device_prefetch(iter(host), strategy, **kw))
    assert len(got) == n - n % (num_steps or 1)
    for g, want in zip(got, host):
        assert "dp" in str(g["x"].sharding.spec)
        for key in ("x", "i"):
            np.testing.assert_array_equal(np.asarray(g[key]), want[key])


def test_restore_checkpoint_tolerates_missing_model_state(tmp_path):
    """A checkpoint saved WITHOUT model_state (pre-r2 layout) still restores
    into a TrainState target (falls back to a target-less restore)."""
    import orbax.checkpoint as ocp

    from tensorflowonspark_tpu.train import checkpoint

    mesh = parallel.build_mesh({"dp": 8})
    strategy = SyncDataParallel(mesh)
    optimizer = optax.sgd(0.1)
    state = strategy.create_state(_linear_init, optimizer, jax.random.PRNGKey(0))

    old_layout = {
        "__train_state__": 1,
        "step": np.asarray(jax.device_get(state.step)),
        "params": jax.device_get(state.params),
        "opt_state": jax.device_get(state.opt_state),
    }
    path = str(tmp_path / "old_ckpt")
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(path, old_layout)
    ckptr.wait_until_finished()

    restored = checkpoint.restore_checkpoint(path, target=jax.device_get(state))
    assert isinstance(restored, TrainState)
    assert restored.model_state == {}
    np.testing.assert_allclose(
        np.asarray(restored.params["w"]), np.asarray(jax.device_get(state.params["w"]))
    )


def test_prune_checkpoints_keeps_newest(tmp_path):
    import os

    from tensorflowonspark_tpu.train import checkpoint

    for step in (2, 4, 6, 10):
        (tmp_path / "ckpt_{}".format(step)).mkdir()
    (tmp_path / "export").mkdir()  # non-numbered dirs are untouched
    (tmp_path / "run_1").mkdir()  # numbered but NOT ckpt_: deletion must
    # never touch user-owned siblings (latest_checkpoint may read them)
    removed = checkpoint.prune_checkpoints(str(tmp_path), keep=2)
    assert removed == 2
    assert sorted(os.listdir(tmp_path)) == ["ckpt_10", "ckpt_6", "export", "run_1"]
    assert checkpoint.latest_checkpoint(str(tmp_path)).endswith("ckpt_10")
    # a user-owned numbered sibling sorting above every ckpt_ dir must not
    # be returned as the resume point (ADVICE r4: it would break the
    # run_with_recovery resume contract)
    (tmp_path / "run_99").mkdir()
    assert checkpoint.latest_checkpoint(str(tmp_path)).endswith("ckpt_10")
    assert checkpoint.latest_checkpoint(str(tmp_path), prefix="").endswith("run_99")
    assert checkpoint.prune_checkpoints(str(tmp_path), keep=0) == 0  # disabled


# -- one step, many layouts -----------------------------------------------------
# What the jitted step owes under every placement: the layout is where the
# arrays live, never what the step computes.

_LM_CFG = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64, dtype="float32")


def _mlp_job(mesh):
    def init(rng):
        k1, k2 = jax.random.split(rng)
        return {
            "w1": jax.random.normal(k1, (64, 64)) * 0.1,
            "w2": jax.random.normal(k2, (64, 8)) * 0.1,
        }

    def loss(params, batch):
        h = jnp.tanh(batch["x"] @ params["w1"])
        return jnp.mean((h @ params["w2"] - batch["y"]) ** 2)

    rng = np.random.default_rng(7)
    batch = {
        "x": rng.normal(size=(16, 64)).astype(np.float32),
        "y": rng.normal(size=(16, 8)).astype(np.float32),
    }
    return init, loss, False, None, batch


def _lm_job(mesh):
    from tensorflowonspark_tpu.models import transformer

    model = transformer.create_model(mesh=mesh, attention="plain", **_LM_CFG)
    rng = np.random.default_rng(7)
    rows, l = 8, 25
    # two documents and a padded tail a row, as the text plane packs them
    seg = np.zeros((rows, l), np.int32)
    pos = np.zeros((rows, l), np.int32)
    seg[:, :13], seg[:, 13:21] = 1, 2
    pos[:, :13], pos[:, 13:21] = np.arange(13), np.arange(8)
    tokens = np.where(seg > 0, rng.integers(3, 64, (rows, l)), 0).astype(np.int32)
    batch = {"tokens": tokens, "segment_ids": seg, "positions": pos}
    return (
        transformer.make_init_fn(model, sample_len=8),
        transformer.make_loss_fn(model),
        True,
        transformer.param_specs,
        batch,
    )


def _job_on(job, axes):
    """``job`` placed on a mesh of ``axes`` over the first devices: the
    strategy, its seeded state, the compiled step and the placed batch."""
    n = int(np.prod(list(axes.values())))
    mesh = parallel.build_mesh(axes, devices=jax.devices()[:n])
    init, loss, has_aux, spec_fn, batch = job(mesh)
    strategy = SyncDataParallel(
        mesh,
        fsdp="fsdp" in axes,
        min_weight_size=1,
        tp=spec_fn if "tp" in axes else False,
    )
    optimizer = optax.sgd(0.1)
    state = strategy.create_state(init, optimizer, jax.random.PRNGKey(0))
    step = strategy.compile_train_step(loss, optimizer, has_aux=has_aux)
    return strategy, state, step, strategy.shard_batch(batch)


def _one_step(job, axes):
    """Loss of the first step, the seeded parameters and every leaf's
    update, on the host."""
    _, state, step, batch = _job_on(job, axes)
    before = jax.tree.map(np.array, state.params)  # the step donates its state
    state, metrics = step(state, batch)
    jax.block_until_ready(metrics["loss"])
    update = jax.tree.map(lambda a, b: np.array(a) - b, state.params, before)
    return float(metrics["loss"]), before, update


@functools.cache
def _one_device(job):
    return _one_step(job, {"dp": 1})


@pytest.mark.parametrize(
    "job,axes",
    [
        (_mlp_job, {"dp": 8}),
        (_mlp_job, {"fsdp": 8}),
        (_lm_job, {"dp": 8}),
        (_lm_job, {"dp": 4, "tp": 2}),
        (_lm_job, {"dp": 2, "fsdp": 2, "tp": 2}),
        (_lm_job, {"fsdp": 8}),
    ],
    ids=["mlp-dp8", "mlp-fsdp8", "lm-dp8", "lm-dp4.tp2", "lm-dp2.fsdp2.tp2", "lm-fsdp8"],
)
def test_step_is_layout_invariant(job, axes):
    """Same seed, same global batch: under every layout the loss and every
    leaf's update are one device's."""
    ref_loss, ref_params, ref_update = _one_device(job)
    loss, params, update = _one_step(job, axes)
    assert abs(loss - ref_loss) <= 1e-5
    names = [
        jax.tree_util.keystr(path)
        for path, _ in jax.tree_util.tree_flatten_with_path(ref_params)[0]
    ]
    for name, got, want in zip(names, jax.tree.leaves(params), jax.tree.leaves(ref_params)):
        np.testing.assert_array_equal(got, want, err_msg="seeded " + name)
    for name, got, want in zip(names, jax.tree.leaves(update), jax.tree.leaves(ref_update)):
        assert np.abs(want).max() > 0, name  # a leaf that never moved compares nothing
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0, err_msg=name)


@pytest.mark.parametrize(
    "axes",
    [{"dp": 2, "fsdp": 4}, {"dp": 4, "tp": 2}, {"dp": 2, "fsdp": 2, "tp": 2}],
    ids=["fsdp", "tp", "fsdp.tp"],
)
def test_params_keep_their_placement_through_steps(axes):
    """After donated steps every parameter (and the step's own outputs, not
    only the seeded state) sits where ``param_shardings`` put it: a leaf that
    came back replicated would compile a second program and hold a whole
    copy a chip."""
    strategy, state, step, batch = _job_on(_lm_job, axes)
    want = strategy.param_shardings(jax.eval_shape(lambda p: p, state.params))
    sharded = set().union(*(_spec_axes(s.spec) for s in jax.tree.leaves(want)))
    assert sharded == set(axes) - {"dp"}
    for _ in range(2):
        state, metrics = step(state, batch)
        jax.block_until_ready(metrics["loss"])
    for (path, leaf), sharding in zip(
        jax.tree_util.tree_flatten_with_path(state.params)[0], jax.tree.leaves(want)
    ):
        assert leaf.sharding.is_equivalent_to(sharding, leaf.ndim), (
            jax.tree_util.keystr(path), leaf.sharding, sharding)


def _collectives(compiled_text):
    """kind -> the distinct replica groups its operations run over."""
    found = {}
    for kind, groups in re.findall(
        r" (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
        r"(?:-start)?\(.*?replica_groups=(\S+?), ",
        compiled_text,
    ):
        found.setdefault(kind, set()).add(groups)
    return found


@pytest.mark.parametrize(
    "axes", [{"dp": 8}, {"fsdp": 8}, {"dp": 4, "tp": 2}], ids=["dp", "fsdp", "dp.tp"]
)
def test_compiled_step_collectives(axes):
    """The kinds of collective XLA derives from the shardings, read in the
    compiled step's text (kinds present or absent, never counts, which its
    combiner may change): dp reduces gradients and gathers nothing (what the
    four-chip smoke run read, PERF.md PR 21); fsdp gathers parameters; tp
    reduces activations over its own axis beside dp's gradients."""
    _, state, step, batch = _job_on(_lm_job, axes)
    found = _collectives(step.lower(state, batch).compile().as_text())
    if "fsdp" in axes:
        assert "all-gather" in found
        assert "all-reduce" in found or "reduce-scatter" in found
    else:
        assert "all-reduce" in found
        assert "all-gather" not in found and "reduce-scatter" not in found
        # dp alone reduces over one grouping of the devices; tp adds its own
        assert (len(found["all-reduce"]) > 1) == ("tp" in axes), found
    assert "all-to-all" not in found


class _FakeDevice:
    def __init__(self, slice_index=None):
        if slice_index is not None:
            self.slice_index = slice_index


class TestMultiSliceWarning:
    def test_distinct_slice_indices_warn(self, caplog):
        from tensorflowonspark_tpu.parallel import mesh

        devs = [_FakeDevice(0), _FakeDevice(0), _FakeDevice(1), _FakeDevice(1)]
        with caplog.at_level("WARNING", logger="tensorflowonspark_tpu.parallel.mesh"):
            slices = mesh._warn_if_multi_slice(devs)
        assert slices == {0, 1}
        assert any("build_hybrid_mesh" in r.message for r in caplog.records)

    def test_single_slice_is_silent(self, caplog):
        from tensorflowonspark_tpu.parallel import mesh

        with caplog.at_level("WARNING", logger="tensorflowonspark_tpu.parallel.mesh"):
            assert mesh._warn_if_multi_slice([_FakeDevice(0), _FakeDevice(0)]) == {0}
        assert not caplog.records

    def test_devices_without_slice_index_are_silent(self, caplog):
        # CPU/virtual devices have no slice_index at all
        from tensorflowonspark_tpu.parallel import mesh

        with caplog.at_level("WARNING", logger="tensorflowonspark_tpu.parallel.mesh"):
            assert mesh._warn_if_multi_slice([_FakeDevice(), _FakeDevice()]) == set()
        assert not caplog.records
