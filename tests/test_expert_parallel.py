"""Expert parallelism on the routed path (``models/decoder.RoutedExperts``,
its ``ep`` placement rules), on the CPU's virtual devices at toy widths: the
layer's own claims (one expert is the ``SwiGLU`` of its matrices; a batch that
crowds one held expert drops nothing), a train step whose experts lie over an
``ep`` axis, and the whole decoder on an ``ep`` mesh held to one device, loss
and gradients, in the three routed dialects."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from decoder_testutil import REF, ROOT, close, hidden, packed_batch, program_config
from tensorflowonspark_tpu import parallel
from tensorflowonspark_tpu.data import text_plane
from tensorflowonspark_tpu.models import decoder, get_model, transformer
from tensorflowonspark_tpu.ops import grouped_matmul as gm
from tensorflowonspark_tpu.train import SyncDataParallel

ROWS, SEQ = 4, 48


def _toy(name):
    with open(os.path.join(ROOT, "examples", "transformer", name)) as f:
        return json.load(f)


def _sigmoid(held):
    """Sigmoid scores with a selection bias, a shared expert, four
    hyper-connected streams: top-2 of 8 experts, one dense layer and two
    routed ones (``decoder_testutil.REF``)."""
    return program_config(REF, experts_held=held)


def _block_diffusion(held):
    """Softmax top-2 of 8 under ``objective: block_diffusion``, no bias and no
    shared expert: ``sdar_toy.json``, narrower."""
    return dict(
        _toy("sdar_toy.json"), vocab_size=96, hidden_size=32, head_dim=8, intermediate_size=80, moe_intermediate_size=16,
        mask_token_id=95, experts_held=held, attention="plain", dtype="float32")


def _softmax_shared(held):
    """Softmax top-3 of 8 beside a shared expert, padding routed nowhere
    (``padding_slots`` false), windowed layers among full ones:
    ``laguna_toy.json``, narrower."""
    return dict(
        _toy("laguna_toy.json"), vocab_size=96, hidden_size=32, head_dim=8, intermediate_size=80,
        moe_intermediate_size=16, shared_expert_intermediate_size=24, padding_slots=False, experts_held=held,
        attention="plain", dtype="float32")


DIALECTS = {"sigmoid_bias_shared_mhc": _sigmoid, "softmax_block_diffusion": _block_diffusion,
            "softmax_shared_no_padding_slots": _softmax_shared}


def _batch(cfg, rows=ROWS, seq=SEQ):
    batch = packed_batch(rows=rows, seq=seq)
    if cfg.get("objective") != "block_diffusion":
        return batch
    tokens, seg, pos = (np.asarray(batch[k])[:, :seq] for k in ("tokens", "segment_ids", "positions"))
    tokens = np.where(seg > 0, np.minimum(tokens, cfg["mask_token_id"] - 1), 0).astype(np.int32)
    noised, weights = text_plane.noise_blocks(
        tokens, seg, pos, cfg["block_length"], cfg["mask_token_id"], 0.05, np.random.default_rng(100))
    return {"tokens": tokens, "noised_tokens": noised, "loss_weights": weights, "segment_ids": seg, "positions": pos}


def _routed_layer(**over):
    """One softmax-routed layer without a shared expert: ``(cfg, init)``."""
    cfg = decoder.DecoderConfig.from_dict(dict(_block_diffusion(None), **over))
    return cfg, lambda x: decoder.RoutedExperts(cfg).init(jax.random.PRNGKey(0), x)["params"]


def test_one_expert_held_here_is_the_swiglu_of_its_matrices():
    """A router of one expert gives it every token at weight 1: the layer is
    ``down(silu(gate x) * up x)`` of that expert's matrices."""
    cfg, init = _routed_layer(num_experts=1, num_experts_per_tok=1)
    x = hidden(0)
    p = init(x)
    y, counts = decoder.RoutedExperts(cfg).apply({"params": p}, x)
    dense = decoder.SwiGLU(cfg, cfg.moe_intermediate_size).apply(
        {"params": {name: {"kernel": p["experts_" + name][0]} for name in ("gate", "up", "down")}}, x)
    close(y, dense, 2e-5)
    assert float(counts["slots_held"]) == float(counts["slots_routed"]) == x.shape[0] * x.shape[1]
    assert float(counts["load_max_over_mean"]) == 1.0


def test_every_token_on_one_held_expert_drops_nothing():
    """No capacity: a batch whose every token picks the same held expert has
    twice the slots the compact buffer holds, the layer falls back to all of
    them and says so, and every token gets its routed term."""
    cfg, init = _routed_layer(num_experts_per_tok=1, experts_held=[2, 2])
    x = hidden(1, rows=2, seq=512).at[..., 0].set(3.0)
    p = init(x)
    p["router"] = p["router"].at[0, 3].set(50.0)
    tokens = x.shape[0] * x.shape[1]
    assert gm.compact_rows(tokens, 2, cfg.n_routed_experts) == tokens // 2
    y, counts = jax.jit(lambda p, x: decoder.RoutedExperts(cfg).apply({"params": p}, x))(p, x)
    assert float(counts["slots_held"]) == float(counts["slots_routed"]) == tokens
    assert (float(counts["layers_at_bound"]), float(counts["layers_compact"])) == (1.0, 0.0)
    assert float(counts["load_max_over_mean"]) == 2.0  # one of the two held experts has them all
    flat = x.reshape(tokens, -1)
    alone = decoder.SwiGLU(cfg, cfg.moe_intermediate_size).apply(
        {"params": {name: {"kernel": p["experts_" + name][1]} for name in ("gate", "up", "down")}}, flat)
    assert bool((jnp.abs(y.reshape(tokens, -1)).sum(-1) > 0).all())
    close(y.reshape(tokens, -1), alone, 2e-5)


def _on_mesh(cfg, axes):
    size = int(np.prod(list(axes.values())))
    mesh = parallel.build_mesh(axes, devices=jax.devices()[:size])
    model = get_model("decoder", mesh=mesh, **cfg)
    return model, SyncDataParallel(mesh, param_spec_fn=decoder.make_param_specs(model))


def test_ep_sharded_train_step():
    """``dp`` 2 x ``ep`` 4, all 8 experts held: the experts' matrices lie over
    ``ep``, the router whole on every chip, and one step of the compiled
    program computes every routed slot."""
    cfg = _sigmoid(None)
    model, strategy = _on_mesh(cfg, {"dp": 2, "ep": 4})
    optimizer = optax.adamw(1e-3)
    state = strategy.create_state(transformer.make_init_fn(model, 8), optimizer, jax.random.PRNGKey(0))
    moe = state.params["layer_1"]["moe"]
    assert moe["experts_gate"].sharding.spec == P("ep", None, None)
    assert moe["experts_down"].sharding.spec == P("ep", None, None)
    assert moe["router"].sharding.spec == P(None, None)
    assert moe["experts_gate"].addressable_shards[0].data.shape[0] == 2  # two of the eight on a chip
    step = strategy.compile_train_step(transformer.make_loss_fn(model), optimizer, has_aux=True)
    state, metrics = step(state, strategy.shard_batch(packed_batch(rows=ROWS, seq=SEQ)))
    jax.block_until_ready(metrics["loss"])
    assert np.isfinite(float(metrics["loss"]))
    routed_layers = sum(kinds[1] == "moe" for kinds in model.cfg.plan)
    assert float(metrics["counter/moe_slots_routed"]) == routed_layers * ROWS * SEQ * cfg["num_experts_per_tok"]
    assert float(metrics["counter/moe_slots_held"]) == float(metrics["counter/moe_slots_routed"])


#: the mesh, the experts held and the batch's rows and length (a share takes the compact slot buffer only where
#: the slots pass ``gm.ROW_TILE``)
MESHES = {
    "all_held-dp2_ep4": ({"dp": 2, "ep": 4}, None, (ROWS, SEQ)),
    "share_compact-dp2_ep2": ({"dp": 2, "ep": 2}, [2, 2], (2, 256)),
    "all_held-dp2_ep2_tp2": ({"dp": 2, "ep": 2, "tp": 2}, None, (ROWS, SEQ)),
}


def _read(model, params, batch):
    """The loss, what the model counted and every leaf's gradient norm."""
    (loss, metrics), grads = jax.jit(jax.value_and_grad(transformer.make_loss_fn(model), has_aux=True))(params, batch)
    counted = {k: float(v) for k, v in metrics.items() if k.startswith("counter/moe_")}
    return loss, counted, {
        jax.tree_util.keystr(path): float(jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)))))
        for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]}


@functools.lru_cache(maxsize=None)
def _on_one_device(dialect, held, shape):
    """``(cfg, batch, params, what one device reads)``, once for the meshes that share them."""
    cfg = DIALECTS[dialect](held and list(held))
    batch = _batch(cfg, *shape)
    alone = get_model("decoder", **cfg)
    params = jax.jit(transformer.make_init_fn(alone, 8))(jax.random.PRNGKey(0))["params"]
    return cfg, batch, params, _read(alone, params, batch)


@pytest.mark.parametrize("placement", list(MESHES))
@pytest.mark.parametrize("dialect", list(DIALECTS))
def test_the_decoder_on_an_ep_mesh_is_the_one_device_decoder(dialect, placement):
    """The same parameters and batch on one device and with the held experts
    over ``ep`` (all eight, or the two of a chip that holds a share and takes
    the compact slot buffer; with a ``tp`` axis beside): the loss, what the
    model counted and every leaf's gradient norm agree (a norm under a ten
    thousandth of the largest, a hyper-connection's leaves whose gradient is
    what cancellation left, to that floor's tolerance)."""
    axes, held, shape = MESHES[placement]
    cfg, batch, params, (want_loss, want_counted, want_norms) = _on_one_device(dialect, held and tuple(held), shape)
    model, strategy = _on_mesh(cfg, axes)
    shardings = strategy.param_shardings(params)
    moe = next(shardings[name]["moe"] for name in sorted(shardings) if "moe" in shardings[name])
    assert moe["experts_gate"].spec[0] == moe["experts_down"].spec[0] == "ep" and moe["router"].spec == P(None, None)
    loss, counted, norms = _read(model, jax.device_put(params, shardings), strategy.shard_batch(batch))

    routed_layers = sum(kinds[1] == "moe" for kinds in model.cfg.plan)
    if held is None:
        assert "counter/moe_layers_compact" not in counted
        if model.cfg.padding_slots:
            assert counted["counter/moe_slots_held"] == counted["counter/moe_slots_routed"]
    else:
        assert counted["counter/moe_layers_compact"] == routed_layers
    assert counted == want_counted
    close(loss, want_loss)
    assert list(norms) == list(want_norms)
    np.testing.assert_allclose(
        list(norms.values()), list(want_norms.values()), rtol=2e-4, atol=2e-4 * 1e-4 * max(want_norms.values()),
        err_msg=str(list(norms)))
