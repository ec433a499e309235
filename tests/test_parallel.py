"""parallel/ package tests on the virtual 8-device CPU mesh (conftest.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tensorflowonspark_tpu import parallel
from tensorflowonspark_tpu.parallel import collectives, mesh as mesh_lib
from tensorflowonspark_tpu.parallel.ring_attention import (
    plain_attention,
    ring_attention_sharded,
)


def test_virtual_device_count():
    assert jax.device_count() == 8


class TestMesh:
    def test_default_is_pure_dp(self):
        m = parallel.build_mesh()
        assert mesh_lib.mesh_shape(m) == {"dp": 8}

    def test_fill_axis(self):
        m = parallel.build_mesh({"dp": -1, "tp": 2})
        assert mesh_lib.mesh_shape(m) == {"dp": 4, "tp": 2}

    def test_axis_order_is_canonical(self):
        m = parallel.build_mesh({"sp": 2, "dp": 2, "tp": 2})
        assert m.axis_names == ("dp", "tp", "sp")

    def test_custom_axis_appended(self):
        m = parallel.build_mesh({"dp": 4, "stage": 2})
        assert m.axis_names == ("dp", "stage")

    def test_bad_product_raises(self):
        with pytest.raises(ValueError):
            parallel.build_mesh({"dp": 3})

    def test_two_fills_raise(self):
        with pytest.raises(ValueError):
            parallel.build_mesh({"dp": -1, "tp": -1})


class TestSharding:
    def test_batch_spec_dp_only(self):
        m = parallel.build_mesh({"dp": 8})
        assert parallel.batch_spec(m) == P("dp")

    def test_batch_spec_dp_fsdp(self):
        m = parallel.build_mesh({"dp": 2, "fsdp": 4})
        assert parallel.batch_spec(m) == P(("dp", "fsdp"))

    def test_fsdp_param_specs(self):
        m = parallel.build_mesh({"fsdp": 8})
        params = {
            "dense": {"kernel": jnp.zeros((256, 128)), "bias": jnp.zeros((128,))},
            "tiny": jnp.zeros((4, 4)),
        }
        specs = parallel.fsdp_param_specs(params, m, min_weight_size=1024)
        assert specs["dense"]["kernel"] == P("fsdp", None)
        assert specs["dense"]["bias"] == P()  # too small
        assert specs["tiny"] == P()

    def test_fsdp_spec_picks_divisible_dim(self):
        m = parallel.build_mesh({"fsdp": 8})
        # first dim (129) not divisible by 8; second (256) is
        specs = parallel.fsdp_param_specs({"w": jnp.zeros((129, 256))}, m, min_weight_size=16)
        assert specs["w"] == P(None, "fsdp")

    def test_shard_batch_and_params_roundtrip(self):
        m = parallel.build_mesh({"dp": 8})
        batch = {"x": np.arange(64, dtype=np.float32).reshape(16, 4)}
        sharded = parallel.shard_batch(batch, m)
        assert sharded["x"].sharding.spec == P("dp")
        np.testing.assert_array_equal(np.asarray(sharded["x"]), batch["x"])

        params = parallel.shard_params({"w": jnp.ones((64, 8))}, m)
        np.testing.assert_array_equal(np.asarray(params["w"]), np.ones((64, 8)))


class TestCollectives:
    def test_psum_pmean_under_shard_map(self):
        m = parallel.build_mesh({"dp": 8})

        def f(x):
            return collectives.psum(x, "dp"), collectives.pmean(x, "dp")

        x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
        s, mu = parallel.shard_map(f, mesh=m, in_specs=P("dp"), out_specs=P("dp"))(x)
        np.testing.assert_allclose(np.asarray(s), np.full((8, 1), 28.0))
        np.testing.assert_allclose(np.asarray(mu), np.full((8, 1), 3.5))

    def test_ring_shift(self):
        m = parallel.build_mesh({"dp": 8})

        def f(x):
            return collectives.ring_shift(x, "dp")

        x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
        out = np.asarray(parallel.shard_map(f, mesh=m, in_specs=P("dp"), out_specs=P("dp"))(x))
        np.testing.assert_array_equal(out[:, 0], np.roll(np.arange(8), 1))

    def test_reduce_scatter(self):
        m = parallel.build_mesh({"dp": 8})

        def f(x):
            return collectives.reduce_scatter(x, "dp")

        # every member holds the full vector; each ends up with its summed slice
        x = jnp.arange(8, dtype=jnp.float32)
        out = np.asarray(parallel.shard_map(f, mesh=m, in_specs=P(), out_specs=P("dp"))(x))
        np.testing.assert_allclose(out, np.arange(8, dtype=np.float32) * 8.0)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_plain_attention(self, causal):
        m = parallel.build_mesh({"dp": 2, "sp": 4})
        rng = np.random.default_rng(0)
        b, h, l, d = 4, 2, 32, 16
        q, k, v = (
            jnp.asarray(rng.standard_normal((b, h, l, d)), jnp.float32) for _ in range(3)
        )
        expected = plain_attention(q, k, v, causal=causal)
        got = ring_attention_sharded(q, k, v, m, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)

    def test_no_sp_axis_falls_back(self):
        m = parallel.build_mesh({"dp": 8})
        rng = np.random.default_rng(1)
        q, k, v = (
            jnp.asarray(rng.standard_normal((2, 2, 8, 4)), jnp.float32) for _ in range(3)
        )
        got = ring_attention_sharded(q, k, v, m, causal=True)
        expected = plain_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)

    def test_gradients_flow(self):
        m = parallel.build_mesh({"sp": 8})
        rng = np.random.default_rng(2)
        b, h, l, d = 2, 2, 32, 8
        q, k, v = (
            jnp.asarray(rng.standard_normal((b, h, l, d)), jnp.float32) for _ in range(3)
        )

        def loss_ring(q, k, v):
            return ring_attention_sharded(q, k, v, m, causal=True).sum()

        def loss_plain(q, k, v):
            return plain_attention(q, k, v, causal=True).sum()

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_plain = jax.grad(loss_plain, argnums=(0, 1, 2))(q, k, v)
        for gr, gp in zip(g_ring, g_plain):
            np.testing.assert_allclose(np.asarray(gr), np.asarray(gp), atol=1e-4)


class TestPipelineParallel:
    """GPipe over the ``pp`` axis (beyond-parity; SURVEY §2.7 row PP):
    pipelined forward/backward must equal the sequential stage composition."""

    def _setup(self):
        import numpy as np

        from tensorflowonspark_tpu import parallel

        mesh = parallel.build_mesh({"pp": 4}, devices=jax.devices()[:4])
        rng = np.random.default_rng(0)
        d = 8
        stage_weights = [
            jnp.asarray(rng.standard_normal((d, d)) / np.sqrt(d), jnp.float32)
            for _ in range(4)
        ]
        stacked = parallel.stack_stage_params(
            [{"w": w} for w in stage_weights]
        )
        x = jnp.asarray(rng.standard_normal((16, d)), jnp.float32)
        return parallel, mesh, stage_weights, stacked, x

    @staticmethod
    def _stage_fn(params, x):
        return jnp.tanh(x @ params["w"])

    def _sequential(self, stage_weights, x):
        for w in stage_weights:
            x = self._stage_fn({"w": w}, x)
        return x

    def test_forward_matches_sequential(self):
        import numpy as np

        parallel, mesh, weights, stacked, x = self._setup()
        mb = parallel.split_microbatches(x, 8)
        out = parallel.pipeline_apply(self._stage_fn, stacked, mb, mesh)
        got = parallel.merge_microbatches(out)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(self._sequential(weights, x)), atol=1e-6
        )

    def test_gradients_match_sequential(self):
        import numpy as np

        parallel, mesh, weights, stacked, x = self._setup()
        mb = parallel.split_microbatches(x, 8)

        def loss_pp(stacked_params):
            out = parallel.pipeline_apply(self._stage_fn, stacked_params, mb, mesh)
            return jnp.sum(out ** 2)

        def loss_seq(stacked_params):
            y = x
            for i in range(4):
                y = self._stage_fn(jax.tree.map(lambda a: a[i], stacked_params), y)
            return jnp.sum(y ** 2)

        g_pp = jax.grad(loss_pp)(stacked)
        g_seq = jax.grad(loss_seq)(stacked)
        np.testing.assert_allclose(
            np.asarray(g_pp["w"]), np.asarray(g_seq["w"]), atol=1e-5
        )

    @staticmethod
    def _mean_loss(y, target):
        return jnp.mean((y - target) ** 2)

    @pytest.mark.parametrize("n_micro", [2, 4])
    def test_loss_and_grads_match_sequential(self, n_micro):
        """Fewer microbatches than stages, and as many: every stage idles
        for part of the schedule and stage 0 re-reads a clipped microbatch;
        the loss against a target and every stage's gradient are still the
        sequential stages'."""
        parallel, mesh, weights, stacked, x = self._setup()
        t = jnp.asarray(np.random.default_rng(1).standard_normal(x.shape), jnp.float32)

        def loss_pp(stacked_params):
            out = parallel.pipeline_apply(
                self._stage_fn, stacked_params,
                parallel.split_microbatches(x, n_micro), mesh,
            )
            return self._mean_loss(parallel.merge_microbatches(out), t)

        def loss_seq(stacked_params):
            y = x
            for i in range(4):
                y = self._stage_fn(jax.tree.map(lambda a: a[i], stacked_params), y)
            return self._mean_loss(y, t)

        loss, grads = jax.value_and_grad(loss_pp)(stacked)
        ref_loss, ref_grads = jax.value_and_grad(loss_seq)(stacked)
        assert abs(float(loss) - float(ref_loss)) <= 1e-6
        np.testing.assert_allclose(
            np.asarray(grads["w"]), np.asarray(ref_grads["w"]), atol=1e-5
        )

    def test_microbatches_weigh_equally_in_the_gradient(self):
        """One stage, four microbatches, each against its own target: the
        gradient through the pipeline is that of the mean of the four
        microbatch losses, so no microbatch is dropped, doubled or handed
        another's slot."""
        mesh = parallel.build_mesh({"pp": 1}, devices=jax.devices()[:1])
        rng = np.random.default_rng(2)
        w = {"w": jnp.asarray(rng.standard_normal((8, 8)) / 4.0, jnp.float32)}
        x = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
        t = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
        xs, ts = parallel.split_microbatches(x, 4), parallel.split_microbatches(t, 4)

        def loss_pp(p):
            out = parallel.pipeline_apply(
                self._stage_fn, parallel.stack_stage_params([p]), xs, mesh
            )
            return jnp.mean(jnp.stack([self._mean_loss(out[m], ts[m]) for m in range(4)]))

        def mean_of_micro(p):
            return jnp.mean(jnp.stack(
                [self._mean_loss(self._stage_fn(p, xs[m]), ts[m]) for m in range(4)]
            ))

        loss, grad = jax.value_and_grad(loss_pp)(w)
        ref_loss, ref_grad = jax.value_and_grad(mean_of_micro)(w)
        assert abs(float(loss) - float(ref_loss)) <= 1e-6
        np.testing.assert_allclose(np.asarray(grad["w"]), np.asarray(ref_grad["w"]), atol=1e-5)

    def test_split_refuses_a_batch_the_count_does_not_divide(self):
        with pytest.raises(ValueError, match="not divisible into 3"):
            parallel.split_microbatches(jnp.zeros((16, 8)), 3)
        assert parallel.split_microbatches(jnp.zeros((16, 8)), 4).shape == (4, 4, 8)

    def test_jit_with_sharded_stage_params(self):
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        parallel, mesh, weights, stacked, x = self._setup()
        stacked = jax.device_put(stacked, NamedSharding(mesh, P("pp")))
        mb = parallel.split_microbatches(x, 8)

        @jax.jit
        def run(params, mb):
            return parallel.pipeline_apply(self._stage_fn, params, mb, mesh)

        out = parallel.merge_microbatches(run(stacked, mb))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(self._sequential(weights, x)), atol=1e-6
        )
