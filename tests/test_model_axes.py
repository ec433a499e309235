"""Model-axis parallelism gates: every model-sharding path — dp×tp,
dp×fsdp×tp, ring attention on real TextPipeline slabs — must be a pure
placement change, never a numerics change. Each path is held to a
numeric-parity gate against its single-axis reference, and the
sharded-param gauges must be live and in range."""

import os

import numpy as np
import pytest

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu import obs, parallel, tfrecord
from tensorflowonspark_tpu.data import TextPipeline, Tokenizer
from tensorflowonspark_tpu.models import transformer
from tensorflowonspark_tpu.train.strategy import SyncDataParallel

CFG = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
           dtype="float32")


def _mesh(axes):
    if jax.device_count() < 8:
        pytest.skip("needs 8 cpu devices (XLA_FLAGS set too late)")
    return parallel.local_mesh(axes)


def _packed_batch(rows=8, l=24, seed=3):
    """Packed [rows, l] batch: two sequences (ids 1, 2) plus a pad tail."""
    rng = np.random.default_rng(seed)
    s1 = rng.integers(3, 64, 11).astype(np.int32)
    s2 = rng.integers(3, 64, 7).astype(np.int32)
    tokens = np.zeros((rows, l), np.int32)
    seg = np.zeros((rows, l), np.int32)
    pos = np.zeros((rows, l), np.int32)
    tokens[:, :11] = s1
    seg[:, :11] = 1
    pos[:, :11] = np.arange(11)
    tokens[:, 11:18] = s2
    seg[:, 11:18] = 2
    pos[:, 11:18] = np.arange(7)
    return tokens, seg, pos


def _ref_params():
    model = transformer.create_model(attention="plain", **CFG)
    return model, model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
    )["params"]


class TestTensorParallel:
    """dp×tp (and dp×fsdp×tp) placement through ``transformer.param_specs``
    must reproduce the replicated model's packed logits bit-for-bit up to
    float tolerance — TP is a layout, not a different network."""

    def _parity(self, strategy, atol=2e-5):
        ref_model, params = _ref_params()
        tokens, seg, pos = _packed_batch()
        ref = ref_model.apply(
            {"params": params}, jnp.asarray(tokens),
            positions=jnp.asarray(pos), segment_ids=jnp.asarray(seg),
        )
        sharded = jax.device_put(params, strategy.param_shardings(params))
        model = transformer.create_model(
            mesh=strategy.mesh, attention="plain", **CFG
        )
        got = model.apply(
            {"params": sharded}, jnp.asarray(tokens),
            positions=jnp.asarray(pos), segment_ids=jnp.asarray(seg),
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=atol)
        return sharded

    def test_dp_tp_logits_match_replicated(self):
        mesh = _mesh({"dp": 2, "tp": 4})
        strategy = SyncDataParallel(mesh, tp=transformer.param_specs)
        sharded = self._parity(strategy)
        axes = {
            a
            for leaf in jax.tree.leaves(sharded)
            for part in leaf.sharding.spec
            if part is not None
            for a in ((part,) if isinstance(part, str) else part)
        }
        assert axes == {"tp"}
        # 2 layers × (q k v o + wi wo) + lm_head all carry a tp dim
        assert obs.gauge("tp_params_sharded").value == 13

    def test_dp_fsdp_tp_overlay_matches_replicated(self):
        mesh = _mesh({"dp": 2, "fsdp": 2, "tp": 2})
        strategy = SyncDataParallel(
            mesh, fsdp=True, min_weight_size=1, tp=transformer.param_specs
        )
        sharded = self._parity(strategy)
        axes = {
            a
            for leaf in jax.tree.leaves(sharded)
            for part in leaf.sharding.spec
            if part is not None
            for a in ((part,) if isinstance(part, str) else part)
        }
        # tp rules place the model dims, the ZeRO-3 overlay shards the rest
        assert "tp" in axes and "fsdp" in axes

    def test_tp_requires_mesh_axis(self):
        mesh = _mesh({"dp": 8})
        with pytest.raises(ValueError, match="'tp' axis"):
            SyncDataParallel(mesh, tp=transformer.param_specs)

    def test_tp_requires_placement_rules(self):
        mesh = _mesh({"dp": 2, "tp": 4})
        with pytest.raises(ValueError, match="placement rules"):
            SyncDataParallel(mesh, tp=True)

    def test_tp_rejects_two_different_rule_fns(self):
        mesh = _mesh({"dp": 2, "tp": 4})
        with pytest.raises(ValueError, match="once"):
            SyncDataParallel(
                mesh, tp=transformer.param_specs,
                param_spec_fn=lambda p, m: p,
            )

    def test_undersized_dims_degrade_to_replicated(self):
        # n_heads=2 cannot shard over tp=4: the head dim must drop its axis
        # (not error), same degrade contract as the fsdp rules
        mesh = _mesh({"dp": 2, "tp": 4})
        cfg = dict(CFG, n_heads=2)
        model = transformer.create_model(attention="plain", **cfg)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
        )["params"]
        specs = transformer.param_specs(params, mesh)
        flat = jax.tree_util.tree_flatten_with_path(specs)[0]
        for path, spec in flat:
            key = "/".join(p.key for p in path)
            if "attn/q/kernel" in key:
                assert spec[1] is None  # H=2 % 4 != 0 → replicated
            if "mlp/wi/kernel" in key:
                assert "tp" in spec  # d_ff=64 still shards


class TestRingOnTextSlabs:
    """Ring attention on real packed [B, L] slabs from TextPipeline — the
    exact tensors the lm workload feeds — at a sequence length that does NOT
    divide the ring, so the pad-to-ring-multiple path runs on real data."""

    def _slab(self, tmp_path, seq_len=46, batch_size=4):
        rng = np.random.default_rng(11)
        words = "ring attention shards long sequence slabs over devices".split()
        texts = [
            " ".join(rng.choice(words, size=max(2, int(rng.lognormal(2.2, 0.7)))))
            for _ in range(96)
        ]
        d = tmp_path / "corpus"
        d.mkdir()
        path = str(d / "part-00000")
        with tfrecord.TFRecordWriter(path) as w:
            for t in texts:
                w.write(t.encode("utf-8"))
        pipe = TextPipeline(
            [path], Tokenizer(kind="word", vocab_size=64),
            seq_len=seq_len, batch_size=batch_size, seed=7,
        )
        batch = next(iter(pipe))
        assert batch["tokens"].shape == (batch_size, seq_len)
        assert (np.asarray(batch["segment_ids"]) > 0).any()
        return batch

    def test_ring_logits_match_plain_on_pipeline_batch(self, tmp_path):
        mesh = _mesh({"dp": 2, "sp": 4})
        batch = self._slab(tmp_path)  # L=46: 46 % 4 != 0 → pad path
        ref_model, params = _ref_params()
        ref = ref_model.apply(
            {"params": params}, jnp.asarray(batch["tokens"]),
            positions=jnp.asarray(batch["positions"]),
            segment_ids=jnp.asarray(batch["segment_ids"]),
        )
        ring = transformer.create_model(mesh=mesh, attention="ring", **CFG)
        got = ring.apply(
            {"params": params}, jnp.asarray(batch["tokens"]),
            positions=jnp.asarray(batch["positions"]),
            segment_ids=jnp.asarray(batch["segment_ids"]),
        )
        real = np.asarray(batch["segment_ids"]) > 0
        np.testing.assert_allclose(
            np.asarray(got)[real], np.asarray(ref)[real], atol=2e-5
        )
