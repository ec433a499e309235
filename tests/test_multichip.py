"""Multi-host placement: hybrid meshes, the fsdp overlay, and the jitted
step in a multi-process gloo world.

Cheapest first:

* pure placement math — ``_hybrid_factors`` / ``_hybrid_device_grid`` /
  ``build_hybrid_mesh`` driven with fake slice-tagged device objects (the
  ``TestMultiSliceWarning`` idiom), asserting DCN-outer/ICI-inner layout;
* the fsdp overlay on tp placement rules;
* a real 2-rank gloo world (``util.spawn_process`` +
  ``testing.join_cpu_world``, the test_jax_distributed pattern): dp across
  the processes × tp across each one's devices, one jitted step.
"""

import json
import os

import numpy as np
import pytest

from tensorflowonspark_tpu import util
from tensorflowonspark_tpu.parallel import mesh as mesh_mod


class _FakeDev:
    """Stands in for a TPU device: identity + slice tag, nothing else."""

    def __init__(self, i, slice_index=None):
        self.id = i
        self.platform = "cpu"
        if slice_index is not None:
            self.slice_index = slice_index

    def __repr__(self):
        return "F{}s{}".format(self.id, getattr(self, "slice_index", "-"))


def _two_slices(per_slice=4):
    return [_FakeDev(i, i // per_slice) for i in range(2 * per_slice)]


class TestHybridFactors:
    def test_sequence_gives_whole_factor_to_first_fit(self):
        f = mesh_mod._hybrid_factors({"dp": 4, "fsdp": 2}, 2, ("dp",))
        assert f == {"dp": 2, "fsdp": 1}

    def test_sequence_skips_non_dividing_axis(self):
        f = mesh_mod._hybrid_factors({"dp": 3, "fsdp": 4}, 2, ("dp", "fsdp"))
        assert f == {"dp": 1, "fsdp": 2}

    def test_no_axis_can_absorb_raises(self):
        with pytest.raises(ValueError, match="absorb the DCN dimension"):
            mesh_mod._hybrid_factors({"tp": 3}, 2, ("dp",))

    def test_dict_split_validated(self):
        f = mesh_mod._hybrid_factors({"dp": 4, "fsdp": 4}, 4, {"dp": 2, "fsdp": 2})
        assert f == {"dp": 2, "fsdp": 2}
        with pytest.raises(ValueError, match="does not divide"):
            mesh_mod._hybrid_factors({"dp": 3}, 2, {"dp": 2})
        with pytest.raises(ValueError, match="multiply to the slice count"):
            mesh_mod._hybrid_factors({"dp": 4, "fsdp": 4}, 4, {"dp": 2})


class TestHybridDeviceGrid:
    def test_slice_major_within_split_axis(self):
        # dp=4 split 2 (DCN) x 2 (ICI): dp rows 0,1 from slice 0, rows 2,3
        # from slice 1 — walking dp crosses the DCN boundary exactly once
        devs = _two_slices(4)
        grid = mesh_mod._hybrid_device_grid(
            {"dp": 4, "fsdp": 2}, {"dp": 2, "fsdp": 1},
            mesh_mod._slice_groups(devs),
        )
        assert grid.shape == (4, 2)
        for j in range(4):
            rows = {d.slice_index for d in grid[j]}
            assert rows == {j // 2}, grid

    def test_unsplit_axis_stays_inside_a_slice(self):
        devs = _two_slices(4)
        grid = mesh_mod._hybrid_device_grid(
            {"dp": 2, "fsdp": 4}, {"dp": 2, "fsdp": 1},
            mesh_mod._slice_groups(devs),
        )
        # fsdp (inner, all-ICI) never leaves a slice; dp crosses slices
        for j in range(2):
            assert {d.slice_index for d in grid[j]} == {j}

    def test_unequal_slices_raise(self):
        devs = [_FakeDev(0, 0), _FakeDev(1, 0), _FakeDev(2, 1)]
        with pytest.raises(ValueError, match="devices; hybrid mesh needs"):
            mesh_mod._hybrid_device_grid(
                {"dp": 3}, {"dp": 1}, mesh_mod._slice_groups(devs)
            )


class TestBuildHybridMesh:
    def test_default_axes_dp_over_slices_fsdp_within(self):
        m = mesh_mod.build_hybrid_mesh(devices=_two_slices(4))
        assert mesh_mod.mesh_shape(m) == {"dp": 2, "fsdp": 4}
        for j in range(2):
            assert {d.slice_index for d in m.devices[j].ravel()} == {j}

    def test_explicit_axes_split_dp(self):
        m = mesh_mod.build_hybrid_mesh({"dp": 4, "fsdp": 2}, devices=_two_slices(4))
        assert mesh_mod.mesh_shape(m) == {"dp": 4, "fsdp": 2}
        for j in range(4):
            assert {d.slice_index for d in m.devices[j].ravel()} == {j // 2}

    def test_single_slice_delegates_to_flat_build(self):
        devs = [_FakeDev(i, 0) for i in range(4)]
        m = mesh_mod.build_hybrid_mesh({"dp": -1}, devices=devs)
        assert mesh_mod.mesh_shape(m) == {"dp": 4}

    def test_drop_trivial_keeps_dcn_axes(self):
        # fsdp==1 is droppable; dp carries the DCN factor and must survive
        m = mesh_mod.build_hybrid_mesh(
            {"dp": 2, "fsdp": 1}, devices=_two_slices(1), drop_trivial=True
        )
        assert mesh_mod.mesh_shape(m) == {"dp": 2}


class TestBuildMeshDelegation:
    """Satellite: build_mesh on a multi-slice world delegates to the hybrid
    placement instead of warning about its own flat reshape."""

    def test_multi_slice_delegates_silently(self, caplog):
        import logging

        with caplog.at_level(logging.WARNING, logger=mesh_mod.__name__):
            m = mesh_mod.build_mesh({"dp": 2, "fsdp": 4}, devices=_two_slices(4))
        assert not caplog.records
        assert mesh_mod.mesh_shape(m) == {"dp": 2, "fsdp": 4}
        for j in range(2):
            assert {d.slice_index for d in m.devices[j].ravel()} == {j}

    def test_unplaceable_falls_back_to_flat_with_warning(self, caplog):
        import logging

        # dp=3 cannot absorb the 2-slice DCN dimension -> hybrid placement
        # fails, the old flat reshape (and its warning) is the fallback
        devs = [_FakeDev(i, i // 3) for i in range(6)]
        with caplog.at_level(logging.WARNING, logger=mesh_mod.__name__):
            m = mesh_mod.build_mesh({"dp": 3, "tp": 2}, devices=devs)
        assert any("flat reshape" in r.getMessage() for r in caplog.records)
        assert mesh_mod.mesh_shape(m) == {"dp": 3, "tp": 2}


class TestFsdpOverlay:
    def test_gauge_counts_sharded_params(self):
        import jax
        import optax

        from tensorflowonspark_tpu import obs, parallel
        from tensorflowonspark_tpu.train import SyncDataParallel

        strategy = SyncDataParallel(
            parallel.local_mesh({"dp": 4, "fsdp": 2}), fsdp=True,
            min_weight_size=1,
        )

        def init_fn(rng):
            k1, k2 = jax.random.split(rng)
            return {
                "w1": jax.random.normal(k1, (64, 64)),
                "w2": jax.random.normal(k2, (64, 8)),
            }

        state = strategy.create_state(init_fn, optax.sgd(0.1), jax.random.PRNGKey(0))
        # both leaves have a dim divisible by the 2-way fsdp axis
        specs = [leaf.sharding.spec for leaf in jax.tree.leaves(state.params)]
        assert all(
            any("fsdp" in ((ax,) if isinstance(ax, str) else tuple(ax or ()))
                for ax in spec)
            for spec in specs
        ), specs
        snap = obs.snapshot()
        assert snap["gauges"]["fsdp_params_sharded"]["value"] == 2

    def test_overlay_respects_existing_specs_and_threshold(self):
        from jax.sharding import PartitionSpec as P

        from tensorflowonspark_tpu import parallel
        from tensorflowonspark_tpu.parallel.sharding import overlay_fsdp_specs

        mesh = parallel.local_mesh({"dp": 4, "fsdp": 2})
        params = {
            "big": np.zeros((64, 64), np.float32),
            "tiny": np.zeros((4,), np.float32),
            "taken": np.zeros((64, 64), np.float32),
        }
        specs = {"big": P(), "tiny": P(), "taken": P(None, "fsdp")}
        out = overlay_fsdp_specs(params, specs, mesh, min_weight_size=64)
        assert out["taken"] == P(None, "fsdp")  # already on fsdp: untouched
        assert out["tiny"] == P()  # under the threshold: replicated
        assert "fsdp" in [ax for ax in out["big"] if ax]  # sharded


# -- multi-process gloo worlds -------------------------------------------------


def _tp_world_member(pid, num_procs, coord_port, out_dir):
    """dp across processes × tp across the member's two local cpu devices."""
    from tensorflowonspark_tpu.testing import join_cpu_world

    join_cpu_world(pid, num_procs, coord_port, local_devices=2)
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from tensorflowonspark_tpu import parallel
    from tensorflowonspark_tpu.train import SyncDataParallel

    def spec_fn(params, mesh):
        # Megatron column/row pair for the 2-layer MLP
        return {"w1": P(None, "tp"), "w2": P("tp", None)}

    def init_fn(rng):
        k1, k2 = jax.random.split(rng)
        return {
            "w1": jax.random.normal(k1, (64, 64)) * 0.1,
            "w2": jax.random.normal(k2, (64, 8)) * 0.1,
        }

    def loss_fn(params, batch):
        h = jnp.tanh(batch["x"] @ params["w1"])
        return jnp.mean((h @ params["w2"] - batch["y"]) ** 2)

    opt = optax.adam(1e-2)
    mesh = parallel.build_mesh({"dp": num_procs, "tp": 2})  # over ALL global devices
    strategy = SyncDataParallel(mesh, tp=spec_fn)
    state = strategy.create_state(init_fn, opt, jax.random.PRNGKey(0))
    step = strategy.compile_train_step(loss_fn, opt)
    rng = np.random.default_rng(100 + pid)  # per-rank rows (the dp axis)
    local = {
        "x": rng.normal(size=(8, 64)).astype(np.float32),
        "y": rng.normal(size=(8, 8)).astype(np.float32),
    }
    losses = []
    for _ in range(4):
        state, metrics = step(state, strategy.shard_batch(local))
        jax.block_until_ready(metrics["loss"])
        losses.append(float(metrics["loss"]))
    axes = {
        ax
        for leaf in jax.tree.leaves(state.params)
        for ax in leaf.sharding.spec
        if isinstance(ax, str)
    }
    out = {"pid": pid, "losses": losses, "tp_sharded_after": "tp" in axes}
    with open(os.path.join(out_dir, "rank{}.json".format(pid)), "w") as f:
        json.dump(out, f)


@pytest.mark.slow
def test_two_rank_dp_tp_world(tmp_path):
    """dp over 2 gloo processes × tp over 2 local cpu devices each, one
    jitted step over the world's mesh: every rank sees the same global-mean
    loss trajectory, training moves, and params stay tp-sharded through
    the donated steps."""
    import functools

    coord_port = util.find_free_port()
    procs = [
        util.spawn_process(
            functools.partial(
                _tp_world_member, pid, 2, coord_port, str(tmp_path)
            ),
            name="tp-{}".format(pid),
        )
        for pid in range(2)
    ]
    for p in procs:
        p.start()
    for p in procs:
        p.join(timeout=300)
    assert all(p.exitcode == 0 for p in procs), [p.exitcode for p in procs]
    results = []
    for pid in range(2):
        with open(tmp_path / "rank{}.json".format(pid)) as f:
            results.append(json.load(f))
    assert results[0]["losses"] == results[1]["losses"]
    assert results[0]["losses"][-1] < results[0]["losses"][0]
    assert all(r["tp_sharded_after"] for r in results)
