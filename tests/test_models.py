"""Model-zoo tests: shapes, one real train step per family, sharded flagship."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from tensorflowonspark_tpu import parallel
from tensorflowonspark_tpu.models import get_model, mnist, resnet, segmentation, transformer
from tensorflowonspark_tpu.train import SyncDataParallel


def test_registry():
    assert get_model("mnist_mlp").hidden == 512
    with pytest.raises(KeyError):
        get_model("nope")


class TestMnist:
    def test_train_step_improves(self):
        mesh = parallel.build_mesh({"dp": 8})
        strategy = SyncDataParallel(mesh)
        model = mnist.create_model("mlp", hidden=32)
        opt = optax.adam(1e-3)
        state = strategy.create_state(mnist.make_init_fn(model), opt, jax.random.PRNGKey(0))
        step = strategy.compile_train_step(mnist.make_loss_fn(model), opt, has_aux=True)
        rng = np.random.default_rng(0)
        batch = strategy.shard_batch(
            {
                "image": rng.standard_normal((32, 28, 28)).astype(np.float32),
                "label": rng.integers(0, 10, 32),
            }
        )
        state, m0 = step(state, batch)
        jax.block_until_ready(m0["loss"])
        for _ in range(20):
            state, m = step(state, batch)
            jax.block_until_ready(m["loss"])
        assert float(m["loss"]) < float(m0["loss"])
        assert "accuracy" in m

    def test_predict_shape(self):
        model = mnist.create_model("cnn")
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)))["params"]
        preds = mnist.make_predict_fn(model)(params, {"image": jnp.zeros((4, 28, 28))})
        assert preds.shape == (4,)


class TestResNet:
    def test_resnet56_train_step_with_batch_stats(self):
        mesh = parallel.build_mesh({"dp": 8})
        strategy = SyncDataParallel(mesh)
        model = resnet.resnet56(num_classes=10)
        opt = optax.sgd(0.1, momentum=0.9)
        state = strategy.create_state(
            resnet.make_init_fn(model, image_size=32), opt, jax.random.PRNGKey(0)
        )
        assert "batch_stats" in state.model_state
        step = strategy.compile_train_step(
            resnet.make_loss_fn(model, weight_decay=1e-4), opt, mutable=True
        )
        rng = np.random.default_rng(0)
        batch = strategy.shard_batch(
            {
                "image": rng.standard_normal((16, 32, 32, 3)).astype(np.float32),
                "label": rng.integers(0, 10, 16),
            }
        )
        before = np.asarray(
            jax.device_get(
                jax.tree.leaves(state.model_state["batch_stats"])[0]
            )
        ).copy()
        state, metrics = step(state, batch)
        jax.block_until_ready(metrics["loss"])
        assert np.isfinite(float(metrics["loss"]))
        after = np.asarray(
            jax.device_get(jax.tree.leaves(state.model_state["batch_stats"])[0])
        )
        assert not np.array_equal(before, after), "batch_stats must update"

    def test_resnet50_forward_shape(self):
        model = resnet.resnet50(num_classes=1000)
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)), train=False)
        logits = model.apply(variables, jnp.zeros((2, 64, 64, 3)), train=False)
        assert logits.shape == (2, 1000)

    def test_resnet50_s2d_stem_matches_shapes_and_trains(self):
        """The space-to-depth stem halves the
        spatial dims exactly like the 7x7/2 stem, so every downstream stage
        sees identical shapes; one train step must run and mutate stats."""
        model = resnet.resnet50(num_classes=1000, stem="imagenet_s2d")
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)), train=False)
        # stem kernel consumes the 2x2-block channels: (4, 4, 12, 64)
        assert variables["params"]["stem"]["kernel"].shape == (4, 4, 12, 64)
        logits = model.apply(variables, jnp.zeros((2, 64, 64, 3)), train=False)
        assert logits.shape == (2, 1000)
        logits, new_state = model.apply(
            variables, jnp.zeros((2, 64, 64, 3)), train=True, mutable=["batch_stats"]
        )
        assert logits.shape == (2, 1000) and "batch_stats" in new_state


class TestSegmentation:
    def test_unet_train_step(self):
        mesh = parallel.build_mesh({"dp": 8})
        strategy = SyncDataParallel(mesh)
        model = segmentation.create_model(num_classes=3, base_filters=8, depth=2)
        opt = optax.adam(1e-3)
        state = strategy.create_state(
            segmentation.make_init_fn(model, image_size=32), opt, jax.random.PRNGKey(0)
        )
        step = strategy.compile_train_step(
            segmentation.make_loss_fn(model), opt, has_aux=True
        )
        rng = np.random.default_rng(0)
        batch = strategy.shard_batch(
            {
                "image": rng.standard_normal((8, 32, 32, 3)).astype(np.float32),
                "mask": rng.integers(0, 3, (8, 32, 32)),
            }
        )
        state, metrics = step(state, batch)
        jax.block_until_ready(metrics["loss"])
        assert np.isfinite(float(metrics["loss"]))
        preds = segmentation.make_predict_fn(model)(state.params, jax.device_get(batch))
        assert preds.shape == (8, 32, 32)


class TestTransformer:
    def test_forward_and_loss(self):
        model = transformer.create_model(
            vocab_size=100, d_model=32, n_layers=2, n_heads=4, d_ff=64
        )
        tokens = jnp.asarray(np.random.default_rng(0).integers(0, 100, (2, 17)))
        variables = model.init(jax.random.PRNGKey(0), tokens)
        logits = model.apply(variables, tokens)
        assert logits.shape == (2, 17, 100)
        loss, aux = transformer.make_loss_fn(model)(variables["params"], {"tokens": tokens})
        assert np.isfinite(float(loss))
        assert float(aux["perplexity"]) > 1

    def test_a_key_the_model_does_not_have_fails_by_name(self):
        """A caller or a configuration file that still asks for the
        switch-routed layer is refused, not ignored."""
        with pytest.raises(TypeError, match="moe_experts"):
            transformer.create_model(vocab_size=100, d_model=32, n_layers=2, n_heads=4, d_ff=64, moe_experts=2)

    def test_causality(self):
        """Changing a future token must not affect earlier logits."""
        model = transformer.create_model(
            vocab_size=50, d_model=16, n_layers=1, n_heads=2, d_ff=32
        )
        rng = np.random.default_rng(1)
        tokens = jnp.asarray(rng.integers(0, 50, (1, 12)))
        variables = model.init(jax.random.PRNGKey(0), tokens)
        logits_a = model.apply(variables, tokens)
        tokens_b = tokens.at[0, -1].set((int(tokens[0, -1]) + 1) % 50)
        logits_b = model.apply(variables, tokens_b)
        np.testing.assert_allclose(
            np.asarray(logits_a[0, :-1]), np.asarray(logits_b[0, :-1]), atol=1e-5
        )

    def test_sharded_train_with_ring_attention(self):
        """Full train step over a dp×sp mesh: ring attention inside the model,
        gradients through ppermute, params updated."""
        mesh = parallel.build_mesh({"dp": 2, "sp": 4})
        strategy = SyncDataParallel(mesh)
        model = transformer.create_model(
            mesh=mesh, vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64
        )
        opt = optax.adam(1e-2)
        state = strategy.create_state(
            transformer.make_init_fn(model, sample_len=8), opt, jax.random.PRNGKey(0)
        )
        step = strategy.compile_train_step(
            transformer.make_loss_fn(model), opt, has_aux=True
        )
        rng = np.random.default_rng(0)
        # tokens [B, 33]: model sees 32 = 4 sp shards of 8
        batch = strategy.shard_batch({"tokens": rng.integers(0, 64, (4, 33))})
        state, m0 = step(state, batch)
        jax.block_until_ready(m0["loss"])
        for _ in range(10):
            state, m = step(state, batch)
            jax.block_until_ready(m["loss"])
        assert float(m["loss"]) < float(m0["loss"])

    def test_ring_matches_unsharded_model(self):
        """Same params, same tokens: sp-sharded ring-attention forward must
        equal the single-device forward."""
        cfg = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64)
        mesh = parallel.build_mesh({"sp": 8})
        model_ring = transformer.create_model(mesh=mesh, **cfg)
        model_plain = transformer.create_model(**cfg)
        tokens = jnp.asarray(np.random.default_rng(2).integers(0, 64, (2, 32)))
        variables = model_plain.init(jax.random.PRNGKey(0), tokens)
        out_plain = model_plain.apply(variables, tokens)
        out_ring = model_ring.apply(variables, tokens)
        np.testing.assert_allclose(
            np.asarray(out_plain), np.asarray(out_ring), atol=3e-5
        )

    def test_flash_attention_impl_matches_plain(self):
        """attention='flash_interpret' (the kernel in the Pallas interpreter)
        must match the plain path."""
        cfg = dict(vocab_size=64, d_model=32, n_layers=1, n_heads=4, d_ff=64)
        model_flash = transformer.create_model(attention="flash_interpret", **cfg)
        model_plain = transformer.create_model(attention="plain", **cfg)
        tokens = jnp.asarray(np.random.default_rng(3).integers(0, 64, (2, 128)))
        variables = model_plain.init(jax.random.PRNGKey(0), tokens)
        out_plain = model_plain.apply(variables, tokens)
        out_flash = model_flash.apply(variables, tokens)
        np.testing.assert_allclose(
            np.asarray(out_plain), np.asarray(out_flash), atol=3e-5
        )

    def test_flash_pads_odd_training_lengths(self):
        """make_loss_fn slices tokens[:, :-1] producing odd seq lengths; the
        flash path must pad-and-slice, matching plain exactly (causality)."""
        cfg = dict(vocab_size=64, d_model=32, n_layers=1, n_heads=4, d_ff=64)
        model_flash = transformer.create_model(attention="flash_interpret", **cfg)
        model_plain = transformer.create_model(attention="plain", **cfg)
        tokens = jnp.asarray(np.random.default_rng(5).integers(0, 64, (1, 515)))
        variables = model_plain.init(jax.random.PRNGKey(0), tokens)
        np.testing.assert_allclose(
            np.asarray(model_plain.apply(variables, tokens)),
            np.asarray(model_flash.apply(variables, tokens)),
            atol=3e-5,
        )

    def test_flash_off_tpu_is_an_error_not_an_interpreted_kernel(self):
        """A run that asked for the chip's kernel must not quietly get the
        interpreter: attention='flash' on a CPU backend raises."""
        model = transformer.create_model(
            attention="flash", vocab_size=16, d_model=8, n_layers=1, n_heads=2, d_ff=16
        )
        with pytest.raises(RuntimeError, match="needs a TPU backend"):
            model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    def test_flash_runs_per_shard_on_a_mesh(self):
        """On a multi-device mesh the kernel goes through shard_map (batch
        over dp, heads over tp): a Mosaic call has no partitioning rule.
        Logits and grads must match the plain path under the same mesh."""
        if jax.device_count() < 8:
            pytest.skip("needs 8 cpu devices")
        from tensorflowonspark_tpu import parallel

        mesh = parallel.local_mesh({"dp": 4, "tp": 2})
        cfg = dict(vocab_size=64, d_model=32, n_layers=1, n_heads=4, d_ff=64)
        flash = transformer.create_model(mesh=mesh, attention="flash_interpret", **cfg)
        plain = transformer.create_model(mesh=mesh, attention="plain", **cfg)
        rng = np.random.default_rng(7)
        # rows of 1024 are 2 x 2 blocks of 512; two rows a dp shard, and the
        # packing differs from shard to shard: shard 0's rows (and row 7) end
        # a document on the block edge, so their lower-left block is
        # skipped; the other rows need theirs. A block map built from the
        # global batch, or indexed by global heads, reads another shard's
        # rows and skips blocks these rows need
        tokens = jnp.asarray(rng.integers(0, 64, (8, 1024)))
        layouts = [[512, 512], [512, 100, 412], [1024], [300, 724], [100] * 10, [40, 900], [], [512, 500]]
        seg = np.zeros((8, 1024), np.int32)
        for row, lengths in zip(seg, layouts):
            at = 0
            for i, n in enumerate(lengths, start=1):
                row[at:at + n] = i
                at += n
        seg = jnp.asarray(seg)
        params = plain.init(jax.random.PRNGKey(0), tokens)["params"]

        def loss(model):
            return jax.jit(jax.value_and_grad(
                lambda p: (model.apply({"params": p}, tokens, segment_ids=seg) ** 2).mean()
            ))(params)

        (l_flash, g_flash), (l_plain, g_plain) = loss(flash), loss(plain)
        np.testing.assert_allclose(float(l_flash), float(l_plain), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(g_flash), jax.tree.leaves(g_plain)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)

    def test_unknown_attention_impl_raises(self):
        model = transformer.create_model(
            attention="flsh", vocab_size=16, d_model=8, n_layers=1, n_heads=2, d_ff=16
        )
        with pytest.raises(ValueError, match="unknown attention impl"):
            model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    def test_forced_plain_on_sp_mesh(self):
        """attention='plain' must win over the mesh's sp axis (debug escape)."""
        mesh = parallel.build_mesh({"sp": 8})
        cfg = dict(vocab_size=32, d_model=16, n_layers=1, n_heads=2, d_ff=32)
        model = transformer.create_model(mesh=mesh, attention="plain", **cfg)
        tokens = jnp.asarray(np.random.default_rng(6).integers(0, 32, (1, 16)))
        variables = model.init(jax.random.PRNGKey(0), tokens)
        out = model.apply(variables, tokens)
        assert np.isfinite(np.asarray(out)).all()

    def test_param_specs_tp_rules(self):
        mesh = parallel.build_mesh({"fsdp": 2, "tp": 4})
        model = transformer.create_model(
            vocab_size=64, d_model=32, n_layers=1, n_heads=4, d_ff=64
        )
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        specs = transformer.param_specs(params, mesh)
        from jax.sharding import PartitionSpec as P

        assert specs["layer_0"]["attn"]["q"]["kernel"] == P("fsdp", "tp", None)
        assert specs["layer_0"]["mlp"]["wo"]["kernel"] == P("tp", "fsdp")
        # vocab-parallel embedding: d_model stays replicated so the gather
        # output lands directly in the activations' layout (no SPMD remat)
        assert specs["embed"]["embedding"] == P("fsdp", None)

