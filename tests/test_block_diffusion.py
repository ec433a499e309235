"""Block diffusion on the normal path, on the CPU at toy sizes and seeded
weights: the block rule against brute force over every pair, the mask kernels
(interpreted) against the dense mask at one and eight query heads a key/value
head, the ``gqa`` kind, softmax top-k routing and the whole model's loss and
gradients against the benchmark's plain reference
(``benchmarks/reference/bd_lm.py``), the eight shares of 16 experts against the
uncut layer, the text plane's noising stage, the example's entry point, and
the causal, segmented programs of the two older LM cells held to what they
were before the kernels learned a second rule."""

import hashlib
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_testutil import ROOT, close, tree_close
from benchmarks.reference import bd_lm as reference
from tensorflowonspark_tpu import obs
from tensorflowonspark_tpu.data import text_plane
from tensorflowonspark_tpu.models import decoder, get_model, transformer
from tensorflowonspark_tpu.ops import flash_attention as fa
from tensorflowonspark_tpu.ops import flash_blocks

#: the reference's configuration (the benchmark file's keys) at toy widths that keep SDAR's ratios: 8 query heads
#: a key/value head, top-4 of 16 experts of which 4 are held, blocks of 4
REF = {
    "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 8, "num_key_value_heads": 1,
    "head_dim": 8, "rope_theta": 1000000, "moe_intermediate_size": 16, "router_experts": 16, "experts_held": [4, 4],
    "num_experts_per_tok": 4, "rms_norm_eps": 1e-6, "block_length": 4, "mask_token_id": 95,
}
SEQ = 64


def program_config(ref=REF, **over):
    cfg = {k: v for k, v in ref.items() if k != "router_experts"}
    cfg.update(num_experts=ref["router_experts"], norm_topk_prob=True, model_type="sdar_moe",
               objective="block_diffusion", attention="plain", dtype="float32")
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def params():
    return reference.init_params(jax.random.PRNGKey(7), REF)


def packed_rows(rows=2, seq=SEQ, seed=0, aligned=False):
    """``(segment_ids, positions)`` of packed rows with a padded tail;
    documents start wherever the last one ended unless ``aligned`` (to 4)."""
    rng = np.random.default_rng(seed)
    seg, pos = np.zeros((rows, seq), np.int32), np.zeros((rows, seq), np.int32)
    for r in range(rows):
        at, doc = 0, 1
        while at < seq - 8:
            n = int(rng.integers(3, seq // 2))
            n = min(n, seq - 5 - at)
            seg[r, at:at + n], pos[r, at:at + n] = doc, np.arange(n)
            at, doc = at + n, doc + 1
            if aligned:
                at = -(-at // 4) * 4
    return seg, pos


def noised_batch(rows=2, seq=SEQ, seed=0, cfg=REF):
    seg, pos = packed_rows(rows, seq, seed)
    rng = np.random.default_rng(seed + 100)
    tokens = (rng.integers(3, cfg["mask_token_id"], seg.shape) * (seg > 0)).astype(np.int32)
    noised, weights = text_plane.noise_blocks(tokens, seg, pos, cfg["block_length"], cfg["mask_token_id"], 0.05, rng)
    return {"tokens": tokens, "noised_tokens": noised, "loss_weights": weights, "segment_ids": seg, "positions": pos}


def doubled(seg, pos, block_length=4):
    block = pos // block_length
    return np.concatenate([seg, seg], 1), np.concatenate([2 * block, 2 * block + 1], 1)


# ---- the rule ------------------------------------------------------------------------------------


def test_marks_say_what_the_rule_says():
    for seed in range(5):
        seg2, labels = doubled(*packed_rows(seed=seed))
        lo, hi, own, key = flash_blocks.bd_marks(seg2, labels)
        by_marks = ((key[:, None, :] >= lo[:, :, None]) & (key[:, None, :] <= hi[:, :, None])) | (
            key[:, None, :] == own[:, :, None])
        assert (by_marks == flash_blocks.bd_mask(seg2, labels)).all()
    # the rule itself, on a row of one document of 6 (blocks of 4 and 2) and a pad: clean 0-6, noised 7-13
    seg2, labels = doubled(np.array([[1] * 6 + [0]]), np.array([[0, 1, 2, 3, 4, 5, 0]]))
    mask = flash_blocks.bd_mask(seg2, labels)[0]
    assert mask[:7, 7:].sum() == 0  # no clean query sees a noised key
    assert mask[1, :7].tolist() == [1, 1, 1, 1, 0, 0, 0] and mask[4, :7].tolist() == [1, 1, 1, 1, 1, 1, 0]
    assert mask[8].tolist() == [0] * 7 + [1, 1, 1, 1, 0, 0, 0]  # a noised query of the first block: its own block
    assert mask[12].tolist() == [1, 1, 1, 1, 0, 0, 0] + [0, 0, 0, 0, 1, 1, 0]  # of the second: clean first block, itself
    assert mask[6].sum() == mask[13].sum() == mask[:, 6].sum() == mask[:, 13].sum() == 0  # padding


@pytest.mark.parametrize("aligned", [True, False])
def test_block_rule_against_brute_force_over_every_pair(aligned):
    """The needed blocks are exactly those with a visible pair, whether or
    not documents start on multiples of 4 (rows of two whole halves)."""
    for seed in range(12):
        seq = (128, 256)[seed % 2]
        seg2, labels = doubled(*packed_rows(rows=3, seq=seq, seed=seed, aligned=aligned))
        mask = flash_blocks.bd_mask(seg2, labels)
        for block in (32, 64):
            n = 2 * seq // block
            brute = mask.reshape(3, n, block, n, block).any((2, 4))
            assert (flash_blocks.needed_blocks(seg2, block, block, labels=labels) == brute).all()
            # the same lines under jit, as the kernels' block map runs them
            marks = jnp.stack(flash_blocks.bd_marks(jnp.asarray(seg2), jnp.asarray(labels), xp=jnp), axis=1)
            (items, longest), _ = fa._block_map(marks, n, n, block, block, False, "block_diffusion")
            assert int(longest) == int(np.maximum(brute.sum(2), 1).sum(1).max())
            if aligned:  # aligned documents keep every needed block at or below the diagonal
                assert not np.triu(brute.any(0), 1).any()


def test_a_block_that_straddles_the_halves_is_kept_not_dropped():
    seg2, labels = doubled(*packed_rows(rows=2, seq=96, seed=3))  # 192 positions in blocks of 64: the middle one straddles
    brute = flash_blocks.bd_mask(seg2, labels).reshape(2, 3, 64, 3, 64).any((2, 4))
    assert (flash_blocks.needed_blocks(seg2, 64, 64, labels=labels) | ~brute).all()


def test_attended_blocks_counts_the_doubled_row_against_its_triangle():
    seg, pos = packed_rows(rows=2, seq=1024, seed=1, aligned=True)
    seg2, labels = doubled(seg, pos)
    needed, dense, steps = flash_blocks.attended_blocks(seg2, labels)
    assert dense == 2 * 10  # 2048 positions in blocks of 512: the triangle of 4
    assert needed == int(flash_blocks.needed_blocks(seg2, 512, 512, labels=labels).sum()) and needed <= steps
    assert needed < dense  # the noised half's off-diagonal blocks among themselves are never needed


# ---- the kernels ---------------------------------------------------------------------------------


def _dense(q, k, v, mask):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(mask[:, None], scores, -1e30), -1), v)


@pytest.mark.parametrize("width", [16, 24], ids=["width16", "width24"])
@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (8, 1), (4, 2)], ids=["group1", "group8", "group2"])
def test_mask_kernels_match_the_dense_mask(heads, kv_heads, width):
    """Documents that start off a multiple of 4, a padded tail, values and all three gradients; at a head size
    whose scale is a power of two and at one whose scale is not (the grouped backward multiplies a key/value
    head's dk by it once, after the group's query heads are summed)."""
    seg2, labels = doubled(*packed_rows(rows=2, seq=256, seed=5))
    mask, real = jnp.asarray(flash_blocks.bd_mask(seg2, labels)), jnp.asarray(seg2 > 0)
    rng = np.random.default_rng(heads)
    q = jnp.asarray(rng.normal(size=(2, heads, 512, width)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, kv_heads, 512, width)), jnp.float32) for _ in range(2))
    weigh = jnp.asarray(rng.normal(size=q.shape), jnp.float32) * real[:, None, :, None]  # padding is not compared

    def kernels(q, k, v):
        return fa.flash_attention(q, k, v, segment_ids=jnp.asarray(seg2), labels=jnp.asarray(labels),
                                  rule="block_diffusion", block_q=128, block_k=128, interpret=True)

    got = jax.value_and_grad(lambda *a: jnp.sum(kernels(*a) * weigh), (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda *a: jnp.sum(_dense(*a, mask) * weigh), (0, 1, 2))(q, k, v)
    # the value is a float32 sum of a hundred thousand signed terms that cancel to about 1: at the new width one
    # order of summation against another is 7e-5 of it (o itself is 7e-7 from float64 at both widths, as the parent's)
    close(got[0], want[0], 1e-5 if width == 16 else 2e-4)
    tree_close(got[1], want[1], 1e-4)


@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (4, 1)], ids=["group1", "group4"])
def test_padding_sees_nothing_and_adds_nothing_under_the_rule(heads, kv_heads):
    """Under the rule a position of padding sees no key, itself included: its
    output is exactly 0, and whatever cotangent it is handed, it gets no dq and
    puts nothing into dk or dv (the kernels' running maximum starts above the
    masked scores, so all its probabilities are 0)."""
    seg2, labels = doubled(*packed_rows(rows=2, seq=128, seed=4))
    pad = jnp.asarray(seg2 == 0)
    assert bool(pad.any())
    rng = np.random.default_rng(heads)
    q = jnp.asarray(rng.normal(size=(2, heads, 256, 24)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, kv_heads, 256, 24)), jnp.float32) for _ in range(2))
    weigh = jnp.asarray(rng.normal(size=q.shape), jnp.float32)

    def kernels(q, k, v):
        return fa.flash_attention(q, k, v, segment_ids=jnp.asarray(seg2), labels=jnp.asarray(labels),
                                  rule="block_diffusion", block_q=64, block_k=64, interpret=True)

    o = kernels(q, k, v)
    assert not np.asarray(o)[np.asarray(pad)[:, None, :, None] & np.ones(o.shape, bool)].any()
    grads = jax.grad(lambda *a: jnp.sum(kernels(*a) * weigh), (0, 1, 2))(q, k, v)
    blind = jax.grad(lambda *a: jnp.sum(kernels(*a) * weigh * ~pad[:, None, :, None]), (0, 1, 2))(q, k, v)
    assert not np.asarray(grads[0] * pad[:, None, :, None]).any()
    for got, want in zip(grads, blind):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("heads,kv_heads", [(8, 1), (4, 2)], ids=["group8", "group2"])
def test_causal_segmented_kernels_take_key_value_groups(heads, kv_heads):
    seg, _ = packed_rows(rows=2, seq=256, seed=6)
    mask = jnp.asarray((seg[:, :, None] == seg[:, None, :]) & (np.arange(256)[:, None] >= np.arange(256)[None, :]))
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, heads, 256, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, kv_heads, 256, 16)), jnp.float32) for _ in range(2))

    def kernels(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, segment_ids=jnp.asarray(seg), block_q=64, block_k=64, interpret=True)

    got = jax.value_and_grad(lambda *a: jnp.sum(kernels(*a) ** 2), (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda *a: jnp.sum(_dense(*a, mask) ** 2), (0, 1, 2))(q, k, v)
    close(got[0], want[0], 1e-5)
    tree_close(got[1], want[1], 1e-4)


def test_the_rule_is_refused_without_its_labels_and_heads_must_divide():
    q = jnp.zeros((1, 4, 128, 8))
    with pytest.raises(ValueError, match="block_diffusion"):
        fa.flash_attention(q, q, q, rule="block_diffusion", segment_ids=jnp.ones((1, 128), jnp.int32), interpret=True)
    with pytest.raises(ValueError, match="unknown rule"):
        fa.flash_attention(q, q, q, rule="dilated", interpret=True)
    with pytest.raises(ValueError, match="do not divide"):
        fa.flash_attention(q, q[:, :3], q[:, :3], causal=True, interpret=True)


#: sha256 of the jaxpr of value-and-gradient of the causal, segmented call at the two older LM cells' shapes. Taken
#: first on the commit before the kernels learned the second rule and the groups (PR 32's tree) and held through PRs
#: 33-36; taken anew on PR 37's tree, which changed what a block's body issues (the scale folded into the exponent,
#: lane-wide row statistics and ids) for every rule alike (this installation's jax)
UNCHANGED = {
    (4, 16, 4096, 64, 64): "ac21454e4479ed34d3eca51f6216f58ff68f832858f83856790fbce24e369ae0",  # lm1024.packed4k
    (1, 32, 8192, 192, 128): "bbd9780257dd9bdd437259c548c51506367d6331f83d99f256753aa5ff244696",  # xing4-a4b.packed8k
}


@pytest.mark.parametrize("shape", sorted(UNCHANGED), ids=["lm1024.packed4k", "xing4-a4b.packed8k"])
def test_the_older_cells_attention_traces_to_what_it_did(shape):
    """The rule and the group size are static: a causal, segmented call with
    one key/value head a query head traces to the same equations, kernels'
    bodies, grids and index maps included, as before there was a second rule."""
    rows, heads, seq, width, value_width = shape
    qk = jax.ShapeDtypeStruct((rows, heads, seq, width), jnp.bfloat16)
    values = jax.ShapeDtypeStruct((rows, heads, seq, value_width), jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((rows, seq), jnp.int32)

    def loss(q, k, v, seg):
        return fa.flash_attention(q, k, v, causal=True, segment_ids=seg).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(qk, qk, values, ids))
    assert "flash_fwd_seg" in text and "flash_bwd_dkv_seg" in text and "_bd" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == UNCHANGED[shape]


# ---- the layer kinds and the model -----------------------------------------------------------------


@pytest.mark.parametrize("impl", ["plain", "flash_interpret"])
def test_grouped_query_attention_matches_reference(params, impl):
    cfg = decoder.DecoderConfig.from_dict(program_config(attention=impl))
    batch = noised_batch()
    _tokens, positions, ids, block, noised = reference.doubled({k: jnp.asarray(v) for k, v in batch.items()}, REF)
    labels = 2 * block + noised
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 2 * SEQ, REF["hidden_size"]), jnp.float32)
    p = params["layer_1"]["attn"]

    def program(p, x):
        return decoder.GroupedQueryAttention(cfg, None, cfg.heads_plan(0)).apply({"params": p}, x, positions, ids, labels)

    def plain(p, x):
        return reference.attention(x, p, positions, ids, block, noised, REF)

    real = (ids > 0)[..., None]  # padding sees nothing on either side: not compared
    weigh = jax.random.normal(jax.random.PRNGKey(2), x.shape) * real

    def both(fn):
        return jax.jit(lambda p, x: (fn(p, x) * real, jax.grad(
            lambda p, x: jnp.sum(fn(p, x) * weigh), argnums=(0, 1))(p, x)))

    (out, grads), (want_out, want) = both(program)(p, x), both(plain)(p, x)
    close(out, want_out)
    tree_close(grads, want, 5e-4)


def test_softmax_routed_experts_match_reference(params):
    cfg = decoder.DecoderConfig.from_dict(program_config())
    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, REF["hidden_size"]), jnp.float32)
    p = params["layer_1"]["moe"]
    assert "router_bias" not in p  # the layer has no such parameter

    def program(p, x):
        return decoder.RoutedExperts(cfg).apply({"params": p}, x)

    y, counts = program(p, x)
    close(y, reference.experts(x, p, REF))
    weights = reference.routing(x.reshape(-1, x.shape[-1]), p["router"], REF)
    close(weights.sum(-1), np.ones(2 * SEQ), 1e-6)  # the chosen four, renormalised
    assert float(counts["slots_routed"]) == 2 * SEQ * 4
    assert float(counts["slots_held"]) == float((weights[:, 4:8] > 0).sum())
    weigh = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    grads = jax.grad(lambda p, x: jnp.sum(program(p, x)[0] * weigh), argnums=(0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(reference.experts(x, p, REF) * weigh), argnums=(0, 1))(p, x)
    tree_close(grads, want, 5e-4)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four chips with 4 of 16 experts each, every one computed as a chip
    would (its own ``experts_held``, the router 16 wide), add up to what the
    uncut reference gives for the whole layer."""
    whole = dict(REF, experts_held=[0, 16])
    p = reference.init_params(jax.random.PRNGKey(9), whole)["layer_0"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, REF["hidden_size"]), jnp.float32)
    total = 0.0
    for first in range(0, 16, 4):
        cfg = decoder.DecoderConfig.from_dict(program_config(experts_held=[first, 4]))
        share = {"router": p["router"], **{k: p[k][first:first + 4] for k in ("experts_gate", "experts_up", "experts_down")}}
        y, counts = decoder.RoutedExperts(cfg).apply({"params": share}, x)
        total = total + y
    close(total, reference.experts(x, p, whole))


@pytest.mark.parametrize("impl,remat", [("plain", False), ("flash_interpret", True)])
def test_model_loss_and_gradients_match_reference(params, impl, remat):
    model = get_model("decoder", **program_config(attention=impl, remat=remat))
    assert model.cfg.plan == (("gqa", "moe", "add"),) * 2
    loss_fn = transformer.make_loss_fn(model)
    batch = noised_batch(seed=2)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(params, batch)
    scale = 1.0 / reference.real_tokens(batch)
    want_loss, want = jax.jit(reference.make_grad_fn(REF))(params, {k: jnp.asarray(v) for k, v in batch.items()}, scale)
    close(loss, want_loss, 1e-5)
    tree_close(grads, want, 5e-4)
    assert int(metrics["masked_positions"]) == int((batch["loss_weights"] > 0).sum()) > 0
    assert float(metrics["counter/moe_slots_routed"]) == 2 * (2 * 2 * SEQ * 4)  # both halves, both layers


def test_published_keys_build_the_model_and_next_token_models_are_untouched():
    with open(os.path.join(ROOT, "benchmarks", "configs", "sdar-30b-a3b.json")) as f:
        published = json.load(f)["source_config"]
    cfg = decoder.DecoderConfig.from_dict(dict(published, experts_held=[0, 16]))
    assert cfg.plan == (("gqa", "moe", "add"),) * 48 and cfg.scoring_func == "softmax" and cfg.objective == "next_token"
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.num_key_value_heads, cfg.head_dim) == (128, 8, 4, 128)
    with pytest.raises(ValueError, match="use_sliding_window"):
        decoder.DecoderConfig.from_dict(dict(published, use_sliding_window=True))
    with pytest.raises(ValueError, match="scoring_func"):
        decoder.DecoderConfig.from_dict(dict(published, scoring_func="sigmoid", topk_method="greedy"))
    with pytest.raises(ValueError, match="objective"):
        decoder.DecoderConfig.from_dict(dict(published, objective="masked"))


# ---- the data plane --------------------------------------------------------------------------------


def test_noise_is_one_rate_a_block_and_weighs_its_inverse():
    seg, pos = packed_rows(rows=4, seq=512, seed=8)
    tokens = np.where(seg > 0, 7, 0).astype(np.int32)
    noised, weights = text_plane.noise_blocks(tokens, seg, pos, 4, 95, 1e-3, np.random.default_rng(0))
    masked = noised == 95
    assert (masked == (weights > 0)).all() and not masked[seg == 0].any()
    assert (noised[~masked] == tokens[~masked]).all()
    # a block's masked positions share one weight, and it is over 1
    block = seg * 1000 + pos // 4
    for r in range(4):
        for b in np.unique(block[r][masked[r]]):
            assert len(set(weights[r][(block[r] == b) & masked[r]])) == 1
    assert weights[masked].min() >= 1.0
    assert 0.4 < masked.sum() / (seg > 0).sum() < 0.6  # t ~ U(0, 1): half on average
    again, _ = text_plane.noise_blocks(tokens, seg, pos, 4, 95, 1e-3, np.random.default_rng(0))
    assert (again == noised).all()


def test_pipeline_noises_aligns_and_counts(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "examples", "transformer"))
    import transformer_spark as example
    from tensorflowonspark_tpu import tfrecord as tfr
    from tensorflowonspark_tpu.data import TextPipeline, Tokenizer

    data_dir = str(tmp_path / "corpus")
    example.make_text_corpus(data_dir, num_shards=2, records_per_shard=64)

    def batches(n):
        pipe = TextPipeline(tfr.list_shards(data_dir), Tokenizer(kind="word", vocab_size=95), seq_len=256, batch_size=2,
                            seed=3, epochs=None, block_diffusion={"block_length": 4, "mask_id": 95})
        stream = iter(pipe)
        out = [next(stream) for _ in range(n)]
        stream.close()
        return out

    before = obs.snapshot()["counters"]
    first, second = batches(3), batches(3)
    after = obs.snapshot()["counters"]
    assert all((a[k] == b[k]).all() for a, b in zip(first, second) for k in a)  # seeded like the packing
    batch = first[0]
    assert sorted(batch) == ["loss_weights", "noised_tokens", "positions", "segment_ids", "tokens"]
    assert batch["loss_weights"].dtype == np.float32 and batch["noised_tokens"].shape == (2, 256)
    seg, pos = batch["segment_ids"], batch["positions"]
    assert (np.nonzero((pos == 0) & (seg > 0))[1] % 4 == 0).all()  # documents start on multiples of 4
    assert batch["tokens"].max() < 95 and (batch["noised_tokens"][batch["loss_weights"] > 0] == 95).all()
    delta = lambda name: after[name]["value"] - before.get(name, {"value": 0})["value"]  # noqa: E731
    # the producer counts what it emitted, which runs a few batches ahead of what was taken
    real = sum(int((b["segment_ids"] > 0).sum()) for b in first + second)
    masked = sum(int((b["loss_weights"] > 0).sum()) for b in first + second)
    assert delta("bd_tokens_real_total") >= real and delta("bd_positions_masked_total") >= masked > 0
    assert 0.3 < delta("bd_positions_masked_total") / delta("bd_tokens_real_total") < 0.7
    assert delta("data_producer_noise_seconds_total") > 0
    # the flash counters follow the row as the model reads it: 512 positions, one block, its triangle one block
    assert delta("flash_blocks_needed_total") == delta("flash_blocks_dense_total") >= 2 * 6
    with pytest.raises(ValueError, match="multiple"):
        TextPipeline(tfr.list_shards(data_dir), Tokenizer(kind="word", vocab_size=95), seq_len=254, batch_size=2,
                     block_diffusion={"block_length": 4, "mask_id": 95})


def test_example_trains_the_toy_configuration_by_block_diffusion(tmp_path, capsys):
    """``transformer_spark.py --model decoder --model_config sdar_toy.json``: the objective is the
    configuration's, the example's ``main_fun`` builds pipeline, model and loss from it, and the loss falls."""
    sys.path.insert(0, os.path.join(ROOT, "examples", "transformer"))
    import transformer_spark as example

    with open(os.path.join(ROOT, "examples", "transformer", "sdar_toy.json")) as f:
        model_cfg = json.load(f)
    data_dir = str(tmp_path / "corpus")
    example.make_text_corpus(data_dir, num_shards=2, records_per_shard=64)
    args = example.build_parser().parse_args([
        "--model", "decoder", "--model_config", "sdar_toy.json", "--data_dir", data_dir, "--seq_len", "64",
        "--batch_size", "8", "--train_steps", "4", "--log_steps", "1", "--tokenizer", "word", "--dtype", "float32",
        "--attention", "flash_interpret",
    ])
    args.model_cfg = model_cfg
    ctx = types.SimpleNamespace(
        initialize_distributed=lambda: None, num_processes=1, num_workers=1, executor_id=0, distributed=False)
    example.main_fun(args, ctx)
    out = capsys.readouterr().out
    assert "step 4: loss" in out and "transformer training complete" in out
    losses = [float(line.split("loss ")[1].split()[0]) for line in out.splitlines() if line.startswith("step ")]
    assert losses[-1] < losses[0]
