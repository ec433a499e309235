"""The plan-built decoder (``models/decoder.py``) against the benchmark's plain
reference (``benchmarks/reference/moe_lm.py``) at toy widths that keep every
ratio of the published model: queries and keys wider than values, 8 routed
experts top-2 of which 2-4 are held here, 4 residual streams, one dense layer
and two routed ones. Seeded weights, float32, values and gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_testutil import (  # noqa: F401  (params is a fixture)
    REF, SEQ, YARN, close, hidden, packed_batch, params, program_config, program_loss, reference, tree_close)
from tensorflowonspark_tpu.models import decoder, transformer
from tensorflowonspark_tpu.ops import grouped_matmul as gm

# ---- the layer kinds, one by one ------------------------------------------------------------------


def test_yarn_frequencies_follow_the_closed_form():
    got = decoder.yarn_inv_freq(8, 10000.0, YARN)
    close(got, reference.yarn_inv_freq(REF), 1e-6)
    # at the published sizes: kept below the correction range (pair 10), divided
    # by the factor above it (pair 23), blended between
    real = dict(YARN, original_max_position_embeddings=4096)
    freq = np.asarray(decoder.yarn_inv_freq(64, 10000.0, real))
    kept = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(freq[:11], kept[:11], rtol=1e-6)
    np.testing.assert_allclose(freq[23:], kept[23:] / 64, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13.0
    np.testing.assert_allclose(freq[11:23], kept[11:23] / 64 * ramp + kept[11:23] * (1 - ramp), rtol=1e-5)
    assert decoder.yarn_mscale(64, 1) == pytest.approx(0.1 * np.log(64) + 1)


@pytest.mark.parametrize("impl", ["plain", "flash_interpret"])
def test_latent_attention_matches_reference(params, impl):
    cfg = decoder.DecoderConfig.from_dict(program_config(REF, attention=impl))
    batch = packed_batch()
    seg, pos = batch["segment_ids"][:, :-1], batch["positions"][:, :-1]
    x, p = hidden(1), params["layer_1"]["attn"]

    def program(p, x):
        return decoder.LatentAttention(cfg).apply({"params": p}, x, pos, seg)

    def plain(p, x):
        return reference.attention(x, p, pos, seg, REF)

    real = (seg > 0)[..., None]  # padded positions attend only each other: not compared
    weigh = jax.random.normal(jax.random.PRNGKey(2), x.shape) * real

    def both(fn):
        return jax.jit(lambda p, x: (fn(p, x) * real, jax.grad(
            lambda p, x: jnp.sum(fn(p, x) * weigh), argnums=(0, 1))(p, x)))

    (out, grads), (want_out, want) = both(program)(p, x), both(plain)(p, x)
    close(out, want_out)
    tree_close(grads, want, 5e-4)


def test_routed_experts_match_reference(params):
    cfg = decoder.DecoderConfig.from_dict(program_config(REF))
    x, p = hidden(3), params["layer_1"]["moe"]

    def program(p, x):
        return decoder.RoutedExperts(cfg).apply({"params": p}, x)

    y, counts = program(p, x)
    close(y, reference.experts(x, p, REF))
    assert float(counts["slots_routed"]) == 2 * SEQ * 2
    weights = reference.routing(x.reshape(-1, x.shape[-1]), p, REF)
    assert float(counts["slots_held"]) == float((weights[:, 2:5] > 0).sum())
    weigh = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    grads = jax.grad(lambda p, x: jnp.sum(program(p, x)[0] * weigh), argnums=(0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(reference.experts(x, p, REF) * weigh), argnums=(0, 1))(p, x)
    tree_close(grads, want, 5e-4)
    assert float(jnp.abs(grads[0]["router_bias"]).max()) == 0.0  # the bias picks, it does not weigh


def test_dense_mlp_matches_reference(params):
    cfg = decoder.DecoderConfig.from_dict(program_config(REF))
    x, p = hidden(5), params["layer_0"]["mlp"]
    got = decoder.SwiGLU(cfg, REF["intermediate_size"]).apply({"params": p}, x)
    want = reference.swiglu(
        x.reshape(-1, x.shape[-1]), p["gate"]["kernel"], p["up"]["kernel"], p["down"]["kernel"], None)
    close(got.reshape(want.shape), want)


def test_hyper_connection_matches_reference(params):
    cfg = decoder.DecoderConfig.from_dict(program_config(REF))
    streams = jax.random.normal(jax.random.PRNGKey(6), (2, SEQ, 4, REF["hidden_size"]), jnp.float32)
    p = params["layer_1"]["res_attn"]

    def sublayer(h):
        return jnp.tanh(h) * 3.0

    def program(p, streams):
        h, maps = decoder.HyperConnection(cfg).apply({"params": p}, streams)
        return decoder.HyperConnection.merge(streams, maps, sublayer(h))

    def plain(p, streams):
        return reference.hyper_connected(streams, p, sublayer, REF)

    close(program(p, streams), plain(p, streams))
    weigh = jax.random.normal(jax.random.PRNGKey(8), streams.shape)
    grads = jax.grad(lambda p, s: jnp.sum(program(p, s) * weigh), argnums=(0, 1))(p, streams)
    want = jax.grad(lambda p, s: jnp.sum(plain(p, s) * weigh), argnums=(0, 1))(p, streams)
    tree_close(grads, want, 5e-4)


@pytest.mark.parametrize("at_clamp", [False, True], ids=["inside_the_clamp", "at_the_clamp"])
def test_mixing_map_is_doubly_stochastic(at_clamp):
    # maps as the model makes them: a diagonal of b_res over small dynamic terms
    logits = 0.5 * jax.random.normal(jax.random.PRNGKey(9), (64, 4, 4), jnp.float32) + 2.0 * jnp.eye(4)
    if at_clamp:  # a diagonal far over the clamp, one entry of every matrix far under it
        logits = logits + 100.0 * jnp.eye(4) - 100.0 * jnp.zeros((4, 4)).at[1, 2].set(1.0)
    clamped = jnp.clip(logits, -30.0, 30.0)
    assert bool((jnp.abs(logits) > 30).any()) == at_clamp
    for m in (decoder.sinkhorn(clamped, 20, 1e-6), reference.sinkhorn(clamped, REF)):
        np.testing.assert_allclose(np.asarray(m.sum(-2)), 1.0, atol=1e-4)  # columns: scaled last
        np.testing.assert_allclose(np.asarray(m.sum(-1)), 1.0, atol=1e-4)  # rows: 20 rounds on
        assert float(m.min()) >= 0.0
    close(decoder.sinkhorn(clamped, 20, 1e-6), reference.sinkhorn(clamped, REF), 1e-5)


# ---- the whole model ------------------------------------------------------------------------------


def reference_loss(params, batch, ref=REF, valid=None):
    with jax.default_matmul_precision("highest"):
        return reference.loss_sum(params, batch, ref) / (valid or reference.valid_targets(batch))


@pytest.mark.parametrize("impl,remat", [("plain", False), ("flash_interpret", True)])
def test_whole_model_matches_reference(params, impl, remat):
    batch = packed_batch()
    _, loss_fn = program_loss(attention=impl, remat=remat)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(params, batch)
    valid = reference.valid_targets(batch)
    want, want_grads = jax.jit(jax.value_and_grad(lambda p, b: reference_loss(p, b, valid=valid)))(params, batch)
    assert float(loss) == pytest.approx(float(want), rel=2e-5)
    # the first sub-layer's pre and mixing maps see equal streams: their exact
    # gradient is 0 and what either side computes there is rounding
    zero = ["layer_0/res_attn/{}_{}".format(kind, name) for kind in ("phi", "alpha", "b") for name in ("pre", "res")]
    tree_close(grads, want_grads, 1e-3, skip=zero)
    for name in zero:
        layer, res, leaf = name.split("/")
        assert float(jnp.abs(want_grads[layer][res][leaf]).max()) < 1e-6
    for layer in ("layer_1", "layer_2"):  # no gradient reaches the selection bias, on either side
        assert float(jnp.abs(grads[layer]["moe"]["router_bias"]).max()) == 0.0
        assert float(jnp.abs(want_grads[layer]["moe"]["router_bias"]).max()) == 0.0
    assert float(metrics["counter/moe_slots_routed"]) == 2 * (2 * SEQ * 2)  # two routed layers
    assert 0 < float(metrics["counter/moe_slots_held"]) < float(metrics["counter/moe_slots_routed"])
    assert float(metrics["gauge/moe_expert_load_max_over_mean"]) >= 1.0


def test_default_plan_and_its_checks():
    cfg = decoder.DecoderConfig.from_dict(program_config(REF))
    assert cfg.plan == (("mla", "swiglu", "mhc"), ("mla", "moe", "mhc"), ("mla", "moe", "mhc"))
    assert cfg.held == (2, 3)
    with pytest.raises(ValueError, match="unknown configuration keys"):
        decoder.DecoderConfig.from_dict(program_config(REF, capacity_factor=1.25))
    with pytest.raises(ValueError, match="scoring_func"):
        decoder.DecoderConfig.from_dict(program_config(REF, scoring_func="tanh"))
    with pytest.raises(ValueError, match="scoring_func"):  # sigmoid scores go with noaux_tc, softmax with neither
        decoder.DecoderConfig.from_dict(program_config(REF, scoring_func="softmax", topk_method="noaux_tc"))
    with pytest.raises(ValueError, match="unknown layer kinds"):
        decoder.DecoderConfig.from_dict(program_config(REF, layer_plan=[["mha", "moe", "mhc"]] * 3)).plan
    # one stream, sequential residual, routed from the first layer: a plan of its own
    plain = decoder.DecoderConfig.from_dict(program_config(
        REF, hc_mult=1, layer_plan=[["mla", "moe", "add"], ["mla", "swiglu", "add"], ["mla", "moe", "add"]]))
    model = decoder.Decoder(plain)
    variables = transformer.make_init_fn(model, 8)(jax.random.PRNGKey(0))
    assert "moe" in variables["params"]["layer_0"] and "mlp" in variables["params"]["layer_1"]
    assert "res_attn" not in variables["params"]["layer_0"]
    logits = model.apply(variables, jnp.ones((1, 8), jnp.int32))
    assert logits.shape == (1, 8, REF["vocab_size"]) and bool(jnp.isfinite(logits).all())


def test_program_names_the_parameters_as_the_reference_does(params):
    model, _ = program_loss()
    own = transformer.make_init_fn(model, 8)(jax.random.PRNGKey(0))["params"]
    assert jax.tree.structure(own) == jax.tree.structure(params)
    assert jax.tree.map(lambda x: x.shape, own) == jax.tree.map(lambda x: x.shape, params)
    assert sum(x.size for x in jax.tree.leaves(own)) == reference.parameter_count(REF)


def test_packed_rows_equal_the_documents_unpacked(params):
    """A row of three packed documents gives each token the logits it gets
    when its document is a row of its own."""
    model, _ = program_loss()
    apply = jax.jit(lambda t, p, s: model.apply({"params": params}, t, positions=p, segment_ids=s))
    batch = packed_batch(rows=1)
    tokens, seg, pos = (batch[k][:, :-1] for k in ("tokens", "segment_ids", "positions"))
    packed = apply(tokens, pos, seg)
    for doc in (1, 3):
        at = np.flatnonzero(np.asarray(seg[0]) == doc)
        alone = apply(tokens[:, at], pos[:, at], jnp.ones((1, len(at)), jnp.int32))
        close(packed[0, at], alone[0], 5e-4)


def test_loss_is_over_the_vocabulary_slice(params):
    """A sliced vocabulary is a smaller vocabulary: ids, logits and the
    cross-entropy's normaliser all over the slice's ``vocab_size`` ids, and
    only targets inside a real segment count."""
    model, loss_fn = program_loss()
    batch = packed_batch()
    logits = jax.jit(lambda b: model.apply(
        {"params": params}, b["tokens"][:, :-1], positions=b["positions"][:, :-1],
        segment_ids=b["segment_ids"][:, :-1]))(batch)
    assert logits.shape[-1] == REF["vocab_size"]
    seg, targets = np.asarray(batch["segment_ids"]), np.asarray(batch["tokens"][:, 1:])
    valid = (seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] > 0)
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    by_hand = -np.take_along_axis(logp, targets[..., None], -1)[..., 0][valid].mean()
    assert float(jax.jit(loss_fn)(params, batch)[0]) == pytest.approx(float(by_hand), rel=1e-5)
    assert float(reference_loss(params, batch)) == pytest.approx(float(by_hand), rel=1e-5)


# ---- the share of the experts ---------------------------------------------------------------------


def test_nothing_is_dropped_under_total_imbalance(params):
    """Every token to the same two experts, both held here: a capacity bound
    would drop nearly all of them; the layer computes them all."""
    p = dict(params["layer_1"]["moe"])
    p["router_bias"] = jnp.zeros(8).at[jnp.array([2, 4])].set(100.0)
    cfg = decoder.DecoderConfig.from_dict(program_config(REF))
    x = hidden(10)
    y, counts = decoder.RoutedExperts(cfg).apply({"params": p}, x)
    assert float(counts["slots_held"]) == float(counts["slots_routed"]) == 2 * SEQ * 2
    assert float(counts["load_max_over_mean"]) == pytest.approx(1.5)  # two of the three held share all
    close(y, reference.experts(x, p, REF))


def test_the_shares_add_up_to_the_uncut_layer(params):
    """Four chips, two experts each: their routed parts, with the shared
    expert counted once, are the whole layer's output as the reference gives
    it with all 8 experts held."""
    whole = dict(REF, experts_held=[0, 8])
    p = reference.init_params(jax.random.PRNGKey(11), whole)["layer_1"]["moe"]
    x = hidden(12)
    want = reference.experts(x, p, whole)
    shared = reference.experts(x, p, whole, held=(0, 0))
    total = shared
    for first in (0, 2, 4, 6):
        cfg = decoder.DecoderConfig.from_dict(program_config(whole, experts_held=[first, 2]))
        share = {k: (v[first:first + 2] if k.startswith("experts_") else v) for k, v in p.items()}
        y, _ = decoder.RoutedExperts(cfg).apply({"params": share}, x)
        total = total + (y - shared)
    close(total, want)


def test_grouped_matmul_fences_the_rows_past_its_groups():
    lhs = jax.random.normal(jax.random.PRNGKey(13), (16, 8), jnp.float32)
    rhs = jax.random.normal(jax.random.PRNGKey(14), (3, 8, 4), jnp.float32)
    order, sizes, local = gm.sort_slots(jnp.array([5, 2, 9, 3, 3, 4, 0, 2, 7, 4, 4, 1, 2, 6, 3, 8], jnp.int32), 2, 3)
    assert sizes.tolist() == [3, 3, 3] and sorted(order.tolist()) == list(range(16))
    assert local.tolist() == [3, 0, 3, 1, 1, 2, 3, 0, 3, 2, 2, 3, 0, 3, 1, 3]  # ``held`` for an expert held elsewhere
    assert order.tolist()[:9] == [1, 7, 12, 3, 4, 14, 5, 9, 10]
    out = gm.grouped_matmul(lhs, rhs, sizes)
    close(out[:3], lhs[:3] @ rhs[0])
    close(out[6:9], lhs[6:9] @ rhs[2])
    assert float(jnp.abs(out[9:]).max()) == 0.0
    d_lhs = jax.grad(lambda a: jnp.sum(gm.grouped_matmul(a, rhs, sizes) ** 2))(lhs)
    assert float(jnp.abs(d_lhs[9:]).max()) == 0.0 and float(jnp.abs(d_lhs[:9]).min()) > 0.0
    place = gm.slot_places(order)
    rows = jax.random.normal(jax.random.PRNGKey(15), (8, 8), jnp.float32)
    d_rows = jax.grad(lambda r: jnp.sum(gm.rows_to_slots(r, order, sizes, 16, 2) * lhs))(rows)
    close(d_rows, jax.grad(lambda r: jnp.sum(r[order // 2] * lhs))(rows))
    d_sorted = jax.grad(lambda s: jnp.sum(gm.slots_to_tokens(s, order, sizes, 2) * rows))(lhs)
    close(d_sorted, jax.grad(lambda s: jnp.sum(s[place].reshape(8, 2, 8).sum(1) * rows))(lhs))
