"""The decoder-hybrid-decoder dialect of ``models/decoder.py`` (``mb_per_layer``:
Mamba layers and windowed differential attention into one full layer whose
keys, values and scan memory gated memory units and cross attention read) at a
small size on the CPU: the plan from the published keys, the model against
``benchmarks/reference/ssm_lm.py`` on seeded weights (loss and every leaf's
gradient), a packed row against its documents run alone, the tied head, what a
recomputed layer keeps and what crosses layers, the refusals, the text plane's
scan counters and the example."""

import json
import math
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.reference import ssm_lm as reference  # noqa: E402

from tensorflowonspark_tpu import models, obs  # noqa: E402
from tensorflowonspark_tpu.models import decoder, transformer  # noqa: E402

with open(os.path.join(ROOT, "examples", "transformer", "phi4flash_toy.json")) as f:
    TOY = json.load(f)  # 6 layers, the published model's 2-7 of 8: M, S, M (kept), F (kept), G, C

LENGTH = 129


def build(attention="plain", **over):
    return models.get_model("decoder", **dict(TOY, attention=attention, **over))


def seeded(seed=3, jitter=0.05):
    """The reference's seeded weights, every leaf moved off its initial value
    (biases and norm weights start at constants that hide a swapped term)."""
    params = reference.init_params(jax.random.PRNGKey(seed), TOY)
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(
        treedef, [leaf + jitter * jax.random.normal(k, leaf.shape) for leaf, k in zip(leaves, keys)])


def packed_batch():
    ids = np.stack([np.concatenate([np.full(50, 1), np.full(60, 2), np.full(19, 0)]), np.full(LENGTH, 1)]).astype(np.int32)
    tokens = np.random.RandomState(0).randint(0, TOY["vocab_size"], ids.shape).astype(np.int32)
    return {"tokens": tokens, "segment_ids": ids, "positions": np.zeros_like(ids)}


def names(tree):
    return ["/".join(p.key for p in path) for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def test_plan_follows_the_published_keys():
    cfg = build().cfg
    assert [kinds[0] for kinds in cfg.plan] == ["mamba", "gqa", "mamba", "gqa", "gmu", "cross"]
    assert {kinds[1:] for kinds in cfg.plan} == {("swiglu", "add")}
    plans = [cfg.heads_plan(i) for i in range(6)]
    assert [p.window for p in plans] == [None, 24, None, None, None, None]  # layer 3 of 8 is the windowed one
    assert [p.hands_on for p in plans] == [False, False, True, True, False, False]  # layers 4 and 5: N/2, N/2 + 1
    for i, plan in enumerate(plans):
        assert plan.lambda_init == pytest.approx(0.8 - 0.6 * math.exp(-0.3 * (2 + i)))
    assert (cfg.d_inner, cfg.dt_rank, decoder.MAMBA_STATES, decoder.MAMBA_TAPS) == (256, 8, 16, 4)
    assert cfg.tie_word_embeddings and not cfg.rotary and not cfg.qk_norm and cfg.attention_bias
    # the benchmark's cut: layers 14-19 of 32
    cell = decoder.DecoderConfig.from_dict(dict(TOY, first_layer=14, model_layers=32, sliding_window=512))
    assert [kinds[0] for kinds in cell.plan] == ["mamba", "gqa", "mamba", "gqa", "gmu", "cross"]
    assert [cell.heads_plan(i).window for i in range(6)] == [None, 512, None, None, None, None]
    assert cell.heads_plan(3).lambda_init == pytest.approx(0.8 - 0.6 * math.exp(-0.3 * 17))
    # the whole model: 9 : 8 : 1 : 7 : 7
    whole = decoder.DecoderConfig.from_dict(dict(TOY, first_layer=0, model_layers=32, num_hidden_layers=32))
    kinds = [k[0] + ("/w" if whole.heads_plan(i).window else "") for i, k in enumerate(whole.plan)]
    assert [kinds.count(k) for k in ("mamba", "gqa/w", "gqa", "gmu", "cross")] == [9, 8, 1, 7, 7]
    # layer_plan stays the override
    assert decoder.DecoderConfig.from_dict(dict(TOY, layer_plan=[["gqa", "swiglu", "add"]] * 6)).plan[0][0] == "gqa"


def test_parameters_are_the_references():
    shapes = jax.eval_shape(transformer.make_init_fn(build(), 8), jax.random.PRNGKey(0))["params"]
    want = reference.init_params(jax.random.PRNGKey(0), TOY)
    assert names(shapes) == names(want)
    assert jax.tree.map(lambda x: x.shape, shapes) == jax.tree.map(lambda x: x.shape, want)
    assert reference.parameter_count(TOY) == sum(x.size for x in jax.tree.leaves(shapes))


@pytest.mark.parametrize("attention", ["plain", "flash_interpret"])
def test_loss_and_every_gradient_match_the_reference(attention):
    params, batch = seeded(), packed_batch()
    (loss, metrics), grads = jax.value_and_grad(transformer.make_loss_fn(build(attention)), has_aux=True)(params, batch)
    want_loss, want = reference.make_grad_fn(TOY)(
        params, {k: jnp.asarray(v) for k, v in batch.items()}, 1.0 / reference.valid_targets(batch))
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    # what crossed layers: the memory [2, 128, 256] and k, v [2, 128, 4, 16] each, float32
    assert float(metrics["counter/ssm_state_carried_bytes"]) == 4 * (2 * 128 * 256 + 2 * 2 * 128 * 4 * 16)
    for name, got, ref in zip(names(grads), jax.tree.leaves(grads), jax.tree.leaves(want)):
        size = float(jnp.linalg.norm(ref))
        if name.endswith("attn/k/bias"):
            # a key's bias moves every score of a softmax row alike: no gradient but rounding
            assert size < 1e-6 and float(jnp.linalg.norm(got)) < 1e-6, name
            continue
        assert size > 1e-4, name  # every other leaf is heard
        assert float(jnp.linalg.norm(got - ref)) <= 1e-4 * size, name


@pytest.mark.parametrize("attention", ["plain", "flash_interpret"])
def test_a_packed_row_equals_its_documents_run_alone(attention):
    """Scan, convolution and all three attention rules (window, whole
    document, cross) see one document at a time."""
    model, params, batch = build(attention), seeded(), packed_batch()
    tokens, ids = batch["tokens"][:1, :-1], batch["segment_ids"][:1, :-1]
    packed = model.apply({"params": params}, tokens, segment_ids=ids, mutable=["counters"])[0]
    for first, last in ((0, 50), (50, 110)):
        alone = model.apply({"params": params}, tokens[:, first:last],
                            segment_ids=np.ones((1, last - first), np.int32), mutable=["counters"])[0]
        np.testing.assert_allclose(packed[0, first:last], alone[0], rtol=2e-4, atol=2e-4)


def test_tied_head_shares_the_matrix_and_untied_does_not():
    tokens = packed_batch()["tokens"][:, :16]
    tied, untied = build(), build(tie_word_embeddings=False)
    shapes = jax.eval_shape(transformer.make_init_fn(untied, 8), jax.random.PRNGKey(0))["params"]
    assert "lm_head" in shapes and shapes["lm_head"]["kernel"].shape == (128, TOY["vocab_size"])
    params = seeded()
    assert "lm_head" not in params
    # logits are LayerNorm(x) E^T: swapping the embedding for twice itself changes the head too
    logits = tied.apply({"params": params}, tokens, mutable=["counters"])[0]
    head = jnp.asarray(params["embed"]["embedding"]).T
    separate = untied.apply({"params": dict(params, lm_head={"kernel": head})}, tokens, mutable=["counters"])[0]
    np.testing.assert_allclose(logits, separate, rtol=1e-5, atol=1e-5)
    doubled = untied.apply({"params": dict(params, lm_head={"kernel": 2 * head})}, tokens, mutable=["counters"])[0]
    np.testing.assert_allclose(doubled, 2 * logits, rtol=1e-5, atol=1e-5)
    # and the embedding's gradient holds the head's term: it is not sparse in the vocabulary
    grad = jax.grad(lambda p: jnp.sum(tied.apply({"params": p}, tokens, mutable=["counters"])[0] ** 2))(params)
    assert int((jnp.abs(grad["embed"]["embedding"]).sum(axis=1) > 0).sum()) == TOY["vocab_size"]
    # a configuration that says nothing keeps the untied head it always had
    assert not decoder.DecoderConfig(vocab_size=8, hidden_size=8, num_hidden_layers=1, num_attention_heads=1).tie_word_embeddings


def test_recomputed_layers_give_the_same_gradients_and_run_each_forward_kernel_once():
    params, batch = seeded(), packed_batch()
    grad = lambda model: jax.grad(lambda p: transformer.make_loss_fn(model)(p, batch)[0])  # noqa: E731
    plain, recomputed = build("flash_interpret"), build("flash_interpret", remat=True)
    for got, want in zip(jax.tree.leaves(grad(recomputed)(params)), jax.tree.leaves(grad(plain)(params))):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    # the scan's results and the flash kernels' are kept: no forward kernel in the recomputed pass, and what
    # crossed layers (the memory, k and v) is a layer's result and its readers' argument, never computed again
    text = str(jax.make_jaxpr(grad(recomputed))(params))
    assert text.count("name=ssm_scan_fwd") == 2 and text.count("name=ssm_scan_bwd") == 2
    assert text.count("name=flash_fwd_win") == 1 and text.count("name=flash_fwd_seg") == 2  # the full layer's and the cross layer's


@pytest.mark.parametrize("change,message", [
    ({"a_new_key": 1}, "unknown configuration keys"),
    ({"mb_per_layer": 3}, "mb_per_layer"),
    ({"embd_pdrop": 0.1}, "embd_pdrop"),
    ({"resid_pdrop": 0.1}, "resid_pdrop"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"lm_head_bias": True}, "lm_head_bias"),
    ({"first_layer": 6, "num_hidden_layers": 2}, "hands on"),  # layers 6-7: readers without the layers they read
], ids=lambda v: next(iter(v)) if isinstance(v, dict) else None)
def test_refusals(change, message):
    with pytest.raises(ValueError, match=message):
        build(**change).cfg.plan


def test_causal_conv_is_the_sum_written_out():
    rng = np.random.RandomState(0)
    xs, kernel, bias = rng.randn(2, 12, 8).astype(np.float32), rng.randn(4, 8).astype(np.float32), rng.randn(8).astype(np.float32)
    ids = np.array([[1] * 5 + [2] * 7, [3] * 12], np.int32)
    want = np.zeros_like(xs)
    for b in range(2):
        for t in range(12):
            total = bias.copy()
            for back in range(4):
                if t - back >= 0 and ids[b, t - back] == ids[b, t]:
                    total += kernel[back] * xs[b, t - back]
            want[b, t] = total / (1 + np.exp(-total))
    np.testing.assert_allclose(decoder.causal_conv(jnp.asarray(xs), kernel, bias, jnp.asarray(ids)), want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        decoder.causal_conv(jnp.asarray(xs), kernel, bias)[1], want[1], rtol=1e-5, atol=1e-6)  # one document a row


def test_param_rules_name_the_new_leaves():
    model = build()
    shapes = jax.eval_shape(transformer.make_init_fn(model, 8), jax.random.PRNGKey(0))["params"]
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    specs = decoder.make_param_specs(model)(shapes, mesh)
    flat = dict(zip(names(shapes), jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))))
    # d_inner is the axis tp splits
    assert flat["layer_0/mamba/in_proj/kernel"] == jax.sharding.PartitionSpec(None, "tp")
    assert flat["layer_0/mamba/a_log"] == jax.sharding.PartitionSpec("tp", None)
    assert flat["layer_0/mamba/out_proj/kernel"] == jax.sharding.PartitionSpec("tp", None)
    assert flat["layer_4/gmu/in_proj/kernel"] == jax.sharding.PartitionSpec(None, "tp")
    assert flat["layer_5/attn/q/kernel"] == jax.sharding.PartitionSpec(None, "tp", None)


def test_text_plane_counts_what_the_scans_restart_on(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "examples", "transformer"))
    import transformer_spark as example

    from tensorflowonspark_tpu import tfrecord as tfr
    from tensorflowonspark_tpu.data import TextPipeline, Tokenizer

    data_dir = str(tmp_path / "corpus")
    example.make_text_corpus(data_dir, num_shards=1, records_per_shard=64)

    def value(name):
        return obs.snapshot()["counters"].get(name, {"value": 0})["value"]

    before = value("ssm_scan_positions_total"), value("ssm_scan_restarts_total")
    pipe = TextPipeline(tfr.list_shards(data_dir), Tokenizer(kind="word", vocab_size=512), seq_len=65, batch_size=4,
                        epochs=1, scan_restarts=True)
    positions = restarts = 0
    for batch in pipe:
        ids = batch["segment_ids"][:, :-1]
        positions += ids.size
        restarts += int((ids[:, 1:] != ids[:, :-1]).sum()) + ids.shape[0]
    assert positions and restarts > positions // 64
    assert (value("ssm_scan_positions_total") - before[0], value("ssm_scan_restarts_total") - before[1]) == (positions, restarts)
    with pytest.raises(ValueError, match="scan_restarts"):
        TextPipeline(tfr.list_shards(data_dir), Tokenizer(kind="word", vocab_size=512), seq_len=64, batch_size=4, scan_restarts=True,
                     block_diffusion={"block_length": 4, "mask_id": 511})


def test_example_trains_the_toy_plan(tmp_path, capsys):
    """``transformer_spark.py --model decoder --model_config phi4flash_toy.json``: the example's ``main_fun``
    builds pipeline, model and loss from the published keys, and the loss falls."""
    sys.path.insert(0, os.path.join(ROOT, "examples", "transformer"))
    import transformer_spark as example

    data_dir = str(tmp_path / "corpus")
    example.make_text_corpus(data_dir, num_shards=2, records_per_shard=64)
    args = example.build_parser().parse_args([
        "--model", "decoder", "--model_config", "phi4flash_toy.json", "--data_dir", data_dir, "--seq_len", "128",
        "--batch_size", "8", "--train_steps", "4", "--log_steps", "1", "--tokenizer", "word", "--dtype", "float32",
        "--attention", "flash_interpret",
    ])
    args.model_cfg = TOY
    ctx = types.SimpleNamespace(
        initialize_distributed=lambda: None, num_processes=1, num_workers=1, executor_id=0, distributed=False)
    before = obs.snapshot()["counters"].get("ssm_scan_restarts_total", {"value": 0})["value"]
    example.main_fun(args, ctx)
    out = capsys.readouterr().out
    assert "step 4: loss" in out and "transformer training complete" in out
    losses = [float(line.split("loss ")[1].split()[0]) for line in out.splitlines() if line.startswith("step ")]
    assert losses[-1] < losses[0]
    assert obs.snapshot()["counters"]["ssm_scan_restarts_total"]["value"] > before
    assert obs.snapshot()["counters"]["ssm_state_carried_bytes_total"]["value"] > 0
