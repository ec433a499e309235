"""Windowed layers among full ones on the normal path, on the CPU at toy sizes
and seeded weights: the window rule's block map and work lists against brute
force over every pair, both kernels (interpreted) against the written-out
mask at 6 and 9 query heads a key/value head, the accepted rules' programs
held to what the parent traced, the ``gqa`` / ``swa`` layers, the routed
layer beside its shared expert and the whole model at a toy plan of the
published shape (2 full + 3 sliding layers, unequal head counts, half rotary
under YaRN, a gate a head, top-3 of 8 experts) against the benchmark's plain
reference (``benchmarks/reference/swa_lm.py``), the four shares of 8 experts
against the uncut layer, what ``from_dict`` refuses, the text plane's window
counters and the example's entry point."""

import hashlib
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_testutil import ROOT, close, packed_batch, tree_close
from benchmarks.reference import swa_lm as reference
from tensorflowonspark_tpu import obs
from tensorflowonspark_tpu.models import decoder, get_model, transformer
from tensorflowonspark_tpu.ops import flash_attention as fa
from tensorflowonspark_tpu.ops import flash_blocks

with open(os.path.join(ROOT, "examples", "transformer", "laguna_toy.json")) as _f:
    TOY = json.load(_f)
#: the reference's configuration (the benchmark file's keys) at the example's toy plan, narrower still: 6 and 9
#: query heads a key/value head, a window of 24, top-3 of 8 experts of which 2 are held, scaling 2.5
REF = dict(
    TOY, vocab_size=96, hidden_size=32, intermediate_size=80, head_dim=8, moe_intermediate_size=16,
    shared_expert_intermediate_size=24, num_experts=2, router_experts=8, experts_held=[2, 2])
SEQ = 48


def program_config(ref=REF, **over):
    cfg = {k: v for k, v in ref.items() if k != "router_experts"}
    cfg.update(num_experts=ref["router_experts"], attention="plain", dtype="float32")
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def params():
    return reference.init_params(jax.random.PRNGKey(7), REF)


def packed_ids(rows=2, seq=256, seed=0, longest=None):
    """Segment ids of packed rows, ``[rows, seq]``: documents of 3 to
    ``longest`` tokens one after another, a padded tail of at least 5."""
    rng = np.random.default_rng(seed)
    seg = np.zeros((rows, seq), np.int32)
    for r in range(rows):
        at, doc = 0, 1
        while at < seq - 8:
            n = min(int(rng.integers(3, longest or seq // 2)), seq - 5 - at)
            seg[r, at:at + n] = doc
            at, doc = at + n, doc + 1
    return seg


# ---- the rule ------------------------------------------------------------------------------------


def test_the_mask_says_what_the_rule_says():
    seg = np.array([[1, 1, 1, 1, 1, 2, 2, 2, 0, 0]])
    mask = flash_blocks.window_mask(seg, 3)[0]
    assert mask[4].tolist() == [0, 0, 1, 1, 1, 0, 0, 0, 0, 0]  # itself and two before it
    assert mask[1].tolist() == [1, 1, 0, 0, 0, 0, 0, 0, 0, 0]  # the document's start cuts the window
    assert mask[6].tolist() == [0, 0, 0, 0, 0, 1, 1, 0, 0, 0]  # and so does the document before
    assert mask[8:].sum() == mask[:, 8:].sum() == 0  # padding
    assert (flash_blocks.window_mask(seg, 10) == flash_blocks.window_mask(seg, 5)).all()  # no document is longer
    assert flash_blocks.visible_pairs(seg, 3) == int(mask.sum()) == (1 + 2 + 3 + 3 + 3) + (1 + 2 + 3)
    assert flash_blocks.visible_pairs(seg) == 15 + 6


@pytest.mark.parametrize("window", [1, 24, 64, 100, 512], ids=lambda w: "window{}".format(w))
def test_window_rule_against_brute_force_over_every_pair(window):
    """The needed blocks are exactly those with a visible pair, for windows
    under, at, over and off a multiple of the block, rows of several
    documents and a padded tail; the pairs counted are the mask's."""
    for seed in range(8):
        seq = (256, 512)[seed % 2]
        seg = packed_ids(rows=3, seq=seq, seed=seed, longest=(None, 40)[seed % 4 == 3])
        mask = flash_blocks.window_mask(seg, window)
        assert flash_blocks.visible_pairs(seg, window) == int(mask.sum())
        for block_q, block_k in ((32, 32), (64, 32), (32, 64)):
            n_q, n_k = seq // block_q, seq // block_k
            brute = mask.reshape(3, n_q, block_q, n_k, block_k).any((2, 4))
            # a block of nothing but padding stands in its own list (the fence of ids lets padding see padding)
            padded = np.einsum("rq,rk->rqk", *(
                (seg.reshape(3, n, -1) == 0).all(-1) for n in (n_q, n_k))) & flash_blocks.window_blocks(
                    n_q, n_k, block_q, block_k, window)[None]
            needed = flash_blocks.needed_blocks(seg, block_q, block_k, window=window)
            assert (needed == (brute | padded)).all()
            assert not (needed & ~flash_blocks.dense_blocks(n_q, n_k, block_q, block_k, window=window)[None]).any()
            # the same lines under jit, as the kernels' block map runs them
            (items, longest), (_, longest_back) = fa._block_map(
                jnp.asarray(seg), n_q, n_k, block_q, block_k, True, ("window", window))
            assert int(longest) == int(np.maximum(needed.sum(2), 1).sum(1).max())
            assert int(longest_back) == int(np.maximum(needed.sum(1), 1).sum(1).max())


def test_ids_in_no_order_drop_no_needed_block():
    rng = np.random.default_rng(0)
    seg = rng.integers(0, 4, (2, 256)).astype(np.int32)
    brute = flash_blocks.window_mask(seg, 40).reshape(2, 8, 32, 8, 32).any((2, 4))
    assert (flash_blocks.needed_blocks(seg, 32, 32, window=40) | ~brute).all()


@pytest.mark.parametrize("window,block", [(24, 32), (100, 64), (128, 64)])
def test_work_lists_walk_the_band_and_nothing_else(window, block):
    """A row's list names its needed blocks once each, outer-major with the
    inner blocks ascending, flagged first and last of their outer block, and
    is no longer than the band allows: the lists' stride under the rule."""
    seg = packed_ids(rows=2, seq=512, seed=3)
    n = 512 // block
    needed = flash_blocks.needed_blocks(seg, block, block, window=window)
    bound = flash_blocks.work_bound(flash_blocks.dense_blocks(n, n, block, block, window=window))
    assert bound < flash_blocks.work_bound(flash_blocks.dense_blocks(n, n, block, block))  # shorter than the triangle
    assert fa._steps(n, n, block, block, True, ("window", window))[0] == bound
    band = flash_blocks.dense_blocks(n, n, block, block, window=window)
    for table, dense in ((needed, band), (needed.swapaxes(1, 2), band.T)):  # q-major (forward), kv-major (backward)
        items, lengths = flash_blocks.work_list(table, flash_blocks.work_bound(dense))
        for row in range(2):
            mine = items[row, :lengths[row]]
            named = [(int(i) >> flash_blocks.ITEM_OUTER_SHIFT,
                      (int(i) >> flash_blocks.ITEM_INNER_SHIFT) & (flash_blocks.ITEM_BLOCKS_MOST - 1))
                     for i in mine if int(i) & flash_blocks.ITEM_COMPUTE]
            assert named == [tuple(at) for at in np.argwhere(table[row])]
            assert sum(bool(int(i) & flash_blocks.ITEM_FIRST) for i in mine) == n
            assert sum(bool(int(i) & flash_blocks.ITEM_LAST) for i in mine) == n


def test_attended_blocks_counts_the_window_against_the_triangle():
    seg = packed_ids(rows=2, seq=2048, seed=1)
    needed, dense, steps = flash_blocks.attended_blocks(seg, window=512)
    full, full_dense, _ = flash_blocks.attended_blocks(seg)
    assert dense == full_dense == 2 * 10  # 2048 positions in blocks of 512: the triangle of 4
    assert needed == int(flash_blocks.needed_blocks(seg, 512, 512, window=512).sum()) <= steps
    assert needed <= full and needed <= 2 * 7  # the band: the diagonal and the one beside it
    assert flash_blocks.block_pairs(seg) == 512 * 512
    assert flash_blocks.visible_pairs(seg, 512) <= needed * flash_blocks.block_pairs(seg)


# ---- the kernels ---------------------------------------------------------------------------------


def _dense(q, k, v, mask):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(mask[:, None], scores, -1e30), -1), v)


@pytest.mark.parametrize("window", [40, 128, 200], ids=lambda w: "window{}".format(w))
@pytest.mark.parametrize("heads,kv_heads", [(6, 1), (9, 1), (12, 2), (2, 2)], ids=["group6", "group9", "group6x2", "group1"])
def test_window_kernels_match_the_written_out_mask(heads, kv_heads, window):
    """Rows of several documents and a padded tail, a window that is not a
    multiple of the block (and one that is), values and all three gradients,
    through the grouped backward at 6 and 9 query heads a key/value head."""
    seg = packed_ids(rows=2, seq=512, seed=5)
    mask, real = jnp.asarray(flash_blocks.window_mask(seg, window)), jnp.asarray(seg > 0)
    rng = np.random.default_rng(heads)
    q = jnp.asarray(rng.normal(size=(2, heads, 512, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, kv_heads, 512, 16)), jnp.float32) for _ in range(2))
    weigh = jnp.asarray(rng.normal(size=q.shape), jnp.float32) * real[:, None, :, None]  # padding is not compared

    def kernels(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, segment_ids=jnp.asarray(seg), rule="window", window=window,
                                  block_q=128, block_k=64, interpret=True)

    got = jax.value_and_grad(lambda *a: jnp.sum(kernels(*a) * weigh), (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda *a: jnp.sum(_dense(*a, mask) * weigh), (0, 1, 2))(q, k, v)
    close(got[0], want[0], 1e-5)
    tree_close(got[1], want[1], 1e-4)


def test_a_window_without_ids_is_one_document_and_a_whole_window_is_the_causal_rule():
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 4, 256, 16)), jnp.float32) for _ in range(3))
    one = np.ones((1, 256), np.int32)
    got = fa.flash_attention(q, k, v, causal=True, rule="window", window=70, block_q=64, block_k=64, interpret=True)
    close(got, _dense(q, k, v, jnp.asarray(flash_blocks.window_mask(one, 70))), 1e-5)
    whole = fa.flash_attention(q, k, v, causal=True, rule="window", window=256, block_q=64, block_k=64, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(whole), np.asarray(fa.flash_attention(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)))


def test_the_rule_is_refused_without_its_window():
    q = jnp.zeros((1, 4, 128, 8))
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, q, q, causal=True, rule="window", interpret=True)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, q, q, causal=False, rule="window", window=16, interpret=True)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, q, q, causal=True, window=16, interpret=True)
    with pytest.raises(ValueError, match="unknown attention rule"):
        transformer._dispatch_attention(q, q, q, "plain", None, rule="dilated")


#: sha256 of the jaxpr of value-and-gradient of a call under the two accepted rules, taken on the parent of the PR
#: that brought the third (f48bd06, this installation's jax): the block-diffusion call at ``sdar-30b-a3b``'s shape and
#: the causal, segmented, grouped call at 48 query heads over 8 (the full layers of the cell the third rule came with)
UNCHANGED = {
    ("block_diffusion", 2, 32, 4, 8192, 128): "d817383dc70ce73e7376726fefa1e4746f7481d3d1ca4d17cbff0963916e4715",
    ("causal", 1, 48, 8, 8192, 128): "48f966fafc34653f5c2660c45e8234559427d6fbd2d5ca6345c80a0412392271",
}


@pytest.mark.parametrize("shape", sorted(UNCHANGED), ids=["sdar-30b-a3b.bd4-packed4k", "grouped-causal-48-over-8"])
def test_the_accepted_rules_trace_to_what_they_did(shape):
    """The rule is static: the calls of the accepted cells trace to the same
    equations, kernels' names, bodies, grids and index maps included, as
    before there was a third (``test_block_diffusion`` holds the two older
    cells' causal calls likewise)."""
    rule, rows, heads, kv_heads, seq, width = shape
    q = jax.ShapeDtypeStruct((rows, heads, seq, width), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((rows, kv_heads, seq, width), jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((rows, seq), jnp.int32)
    if rule == "block_diffusion":
        def loss(q, k, v, seg, labels):
            return fa.flash_attention(q, k, v, segment_ids=seg, rule=rule, labels=labels).astype(jnp.float32).sum()
        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, k, ids, ids))
    else:
        def loss(q, k, v, seg):
            return fa.flash_attention(q, k, v, causal=True, segment_ids=seg).astype(jnp.float32).sum()
        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, k, ids))
    assert "_win" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == UNCHANGED[shape]


def test_the_window_kernels_bear_their_own_names():
    q = jax.ShapeDtypeStruct((1, 72, 1024, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 8, 1024, 128), jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((1, 1024), jnp.int32)

    def loss(q, k, v, seg):
        return fa.flash_attention(q, k, v, causal=True, segment_ids=seg, rule="window", window=512).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, k, ids))
    assert "flash_fwd_win" in text and "flash_bwd_dkv_win" in text and "_seg" not in text


# ---- the layer kinds and the model -----------------------------------------------------------------


def test_the_plan_reads_the_published_lists_layer_by_layer():
    cfg = decoder.DecoderConfig.from_dict(program_config())
    assert cfg.plan == (("gqa", "swiglu", "add"),) + (("gqa", "moe", "add"),) * 4
    full, windowed = cfg.heads_plan(4), cfg.heads_plan(2)
    assert (full.heads, full.window, full.gate) == (12, None, True) and dict(full.rope)["rope_type"] == "yarn"
    assert (windowed.heads, windowed.window, windowed.gate) == (18, 24, True)
    assert dict(windowed.rope) == {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}
    assert (cfg.shared_width, cfg.routed_scaling_factor, cfg.scoring_func, cfg.qk_norm) == (24, 2.5, "softmax", False)
    # a cut in depth keeps the published lists: they are read as far as the model is deep
    cut = decoder.DecoderConfig.from_dict(program_config(num_hidden_layers=2))
    assert cut.plan == (("gqa", "swiglu", "add"), ("gqa", "moe", "add")) and cut.num_attention_heads_per_layer == (12, 18)
    assert (cut.heads_plan(0).window, cut.heads_plan(1).window) == (None, 24)


def test_published_keys_build_the_model():
    with open(os.path.join(ROOT, "benchmarks", "configs", "laguna-s-2-1.json")) as f:
        published = json.load(f)["source_config"]
    cfg = decoder.DecoderConfig.from_dict(dict(published, experts_held=[0, 8], qk_norm=False))
    assert len(cfg.plan) == 48 and cfg.plan[0] == ("gqa", "swiglu", "add") and cfg.plan[4] == ("gqa", "moe", "add")
    assert [cfg.heads_plan(i).window for i in range(9)] == [None, 512, 512, 512, None, 512, 512, 512, None]
    assert all(kinds[1] == "moe" for kinds in cfg.plan[1:])
    assert (cfg.heads_plan(0).heads, cfg.heads_plan(1).heads, cfg.heads_plan(1).window) == (48, 72, 512)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.num_key_value_heads, cfg.head_dim) == (256, 10, 8, 128)
    assert (cfg.shared_width, cfg.routed_scaling_factor, cfg.intermediate_size) == (1024, 2.5, 12288)


@pytest.mark.parametrize("over,named", [
    ({"gating": "per-layer"}, "gating"),
    ({"gating_types": ["per_head", "per_layer", "per_head", "per_head", "per_head"]}, "gating_types"),
    ({"moe_router_logit_softcapping": 30.0}, "moe_router_logit_softcapping"),
    ({"moe_apply_router_weight_on_input": True}, "moe_apply_router_weight_on_input"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"sliding_window": None}, "sliding_window"),
    ({"layer_types": ["full_attention", "chunked_attention", "full_attention", "full_attention", "full_attention"]},
     "layer_types"),
    ({"mlp_only_layers": [1]}, "mlp_layer_types"),
    ({"num_attention_heads_per_layer": [12, 18]}, "num_attention_heads_per_layer"),
    ({"rope_parameters": {"full_attention": {"rope_type": "llama3", "rope_theta": 1e4}}}, "rope_parameters"),
    ({"attn_logit_softcapping": 50.0}, "unknown configuration keys"),
], ids=lambda x: x if isinstance(x, str) else "")
def test_from_dict_refuses_by_name_what_it_does_not_implement(over, named):
    with pytest.raises(ValueError, match=named):
        decoder.DecoderConfig.from_dict(program_config(**over))


def _layer_inputs(seed=1):
    batch = packed_batch(seed=seed)
    positions, ids = (jnp.asarray(batch[name][:, :-1]) for name in ("positions", "segment_ids"))
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, SEQ, REF["hidden_size"]), jnp.float32)
    return x, positions, ids


@pytest.mark.parametrize("impl", ["plain", "flash_interpret"])
@pytest.mark.parametrize("index", [1, 4], ids=["sliding-18-heads", "full-12-heads-yarn"])
def test_attention_layers_match_reference(params, index, impl):
    """A windowed layer (18 query heads over 2, plain rotary over the whole
    head, window 24) and a full one (12 over 2, YaRN on half the head), each
    with its gate, outputs and gradients."""
    cfg = decoder.DecoderConfig.from_dict(program_config(attention=impl))
    x, positions, ids = _layer_inputs()
    p = params["layer_{}".format(index)]["attn"]

    def program(p, x):
        return decoder.GroupedQueryAttention(cfg, None, cfg.heads_plan(index)).apply({"params": p}, x, positions, ids)

    def plain(p, x):
        return reference.attention(x, p, positions, ids, REF, index)

    real = (ids > 0)[..., None]  # what padding sees is the path's own business: not compared
    weigh = jax.random.normal(jax.random.PRNGKey(2), x.shape) * real

    def both(fn):
        return jax.jit(lambda p, x: (fn(p, x) * real, jax.grad(
            lambda p, x: jnp.sum(fn(p, x) * weigh), argnums=(0, 1))(p, x)))

    (out, grads), (want_out, want) = both(program)(p, x), both(plain)(p, x)
    close(out, want_out)
    tree_close(grads, want, 5e-4)
    assert float(jnp.abs(grads[0]["gate"]["kernel"]).max()) > 0


def test_the_window_changes_what_a_layer_computes(params):
    """Documents longer than the window: the same weights under the causal
    rule give another output (the comparison above is not vacuous)."""
    cfg = decoder.DecoderConfig.from_dict(program_config())
    x, positions, ids = _layer_inputs()
    p = params["layer_1"]["attn"]
    windowed = decoder.GroupedQueryAttention(cfg, None, cfg.heads_plan(1)).apply({"params": p}, x, positions, ids)
    whole = decoder.GroupedQueryAttention(
        cfg, None, decoder.HeadsPlan(18, None, cfg.heads_plan(1).rope, True)).apply({"params": p}, x, positions, ids)
    assert float(jnp.abs(windowed - whole).max()) > 1e-3


def test_routed_experts_beside_a_shared_one_match_reference(params):
    cfg = decoder.DecoderConfig.from_dict(program_config())
    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, REF["hidden_size"]), jnp.float32)
    p = params["layer_2"]["moe"]
    assert "router_bias" not in p and "shared" in p

    def program(p, x):
        return decoder.RoutedExperts(cfg).apply({"params": p}, x)

    y, counts = program(p, x)
    close(y, reference.experts(x, p, REF))
    weights = reference.routing(x.reshape(-1, x.shape[-1]), p["router"], REF)
    close(weights.sum(-1), 2.5 * np.ones(2 * SEQ), 1e-6)  # the chosen three, renormalised, times the scaling
    assert float(counts["slots_routed"]) == 2 * SEQ * 3
    assert float(counts["slots_held"]) == float((weights[:, 2:4] > 0).sum())
    weigh = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    grads = jax.grad(lambda p, x: jnp.sum(program(p, x)[0] * weigh), argnums=(0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(reference.experts(x, p, REF) * weigh), argnums=(0, 1))(p, x)
    tree_close(grads, want, 5e-4)


def test_padding_takes_no_slots_where_the_configuration_says_so(params):
    """``padding_slots: false``: positions of segment id 0 reach no held
    expert (the shared one still sees them), the real positions' outputs and
    every gradient they send are those of the layer that routes its padding,
    and the held slots are the real positions'."""
    x, _, ids = _layer_inputs()
    ids = ids.at[1, SEQ // 2:].set(0)  # a leftover row: half of it padding
    # ... all of it one token at one position, as a packed row's padding is
    x = jnp.where((ids > 0)[..., None], x, x[0, 0])
    p, real = params["layer_2"]["moe"], (ids > 0)[..., None]
    weigh = jax.random.normal(jax.random.PRNGKey(4), x.shape) * real

    def layer(**over):
        cfg = decoder.DecoderConfig.from_dict(program_config(**over))
        y, counts = decoder.RoutedExperts(cfg).apply({"params": p}, x, ids)
        grads = jax.grad(lambda p, x: jnp.sum(decoder.RoutedExperts(cfg).apply({"params": p}, x, ids)[0] * weigh),
                         argnums=(0, 1))(p, x)
        return y, counts, grads

    (y, counts, grads), (routed_y, routed_counts, routed_grads) = layer(padding_slots=False), layer()
    close(y * real, routed_y * real)
    tree_close(grads, routed_grads, 1e-5)
    shared = reference.experts(x, p, REF, held=(0, 0))
    close(jnp.where(real, 0, y), jnp.where(real, 0, shared))
    weights = reference.routing(x.reshape(-1, x.shape[-1]), p["router"], REF)
    assert float(counts["slots_held"]) == float(((weights[:, 2:4] > 0) * real.reshape(-1, 1)).sum())
    assert float(counts["slots_held"]) < float(routed_counts["slots_held"]) == float((weights[:, 2:4] > 0).sum())
    # without segment ids nothing is padding
    cfg = decoder.DecoderConfig.from_dict(program_config(padding_slots=False))
    close(decoder.RoutedExperts(cfg).apply({"params": p}, x)[0], routed_y)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four chips with 2 of 8 experts each, every one computed as a chip
    would (its own ``experts_held``, the router 8 wide, the scaling of 2.5),
    and the shared expert, which every chip computes alike, counted once, add
    up to what the uncut reference gives for the whole layer."""
    whole = dict(REF, experts_held=[0, 8])
    p = reference.init_params(jax.random.PRNGKey(9), whole)["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, REF["hidden_size"]), jnp.float32)
    alone = reference.experts(x, p, whole, held=(0, 0))  # the shared expert and nothing else
    total = alone
    for first in range(0, 8, 2):
        cfg = decoder.DecoderConfig.from_dict(program_config(experts_held=[first, 2]))
        share = dict(p, **{k: p[k][first:first + 2] for k in ("experts_gate", "experts_up", "experts_down")})
        y, counts = decoder.RoutedExperts(cfg).apply({"params": share}, x)
        total = total + (y - alone)
    close(total, reference.experts(x, p, whole))
    assert float(jnp.abs(alone).max()) > 1e-2 and float(jnp.abs(total - alone).max()) > 1e-2


@pytest.mark.parametrize("impl,remat", [("plain", False), ("flash_interpret", True)])
def test_model_loss_and_gradients_match_reference(params, impl, remat):
    model = get_model("decoder", **program_config(attention=impl, remat=remat))
    loss_fn = transformer.make_loss_fn(model)
    batch = packed_batch(seed=2)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(params, batch)
    scale = 1.0 / reference.valid_targets(batch)
    want_loss, want = jax.jit(reference.make_grad_fn(REF))(params, {k: jnp.asarray(v) for k, v in batch.items()}, scale)
    close(loss, want_loss, 1e-5)
    tree_close(grads, want, 5e-4)
    assert set(jax.tree_util.tree_structure(grads).node_data()[1]) == set(params)
    assert float(metrics["counter/moe_slots_routed"]) == 4 * (2 * SEQ * 3)  # the four routed layers


def test_calibrated_routers_send_the_held_experts_their_even_share(params):
    key = jax.random.PRNGKey(7)
    batch = {k: jnp.asarray(v) for k, v in packed_batch(rows=4, seed=3).items()}
    routers = reference.calibrated_routers(key, REF, batch)
    assert sorted(routers) == ["layer_1", "layer_2", "layer_3", "layer_4"]
    model = get_model("decoder", **program_config(padding_slots=False))
    _, metrics = transformer.make_loss_fn(model)(reference.init_params(key, REF, routers), batch)
    # the real positions' slots: padding takes none in this program, and the calibration counts none
    real_slots = 4 * 3 * int((batch["segment_ids"][:, :-1] > 0).sum())
    assert abs(float(metrics["counter/moe_slots_held"]) / real_slots - 2 / 8) < 0.01


def test_parameter_rules_place_the_gate_with_its_heads():
    cfg = decoder.DecoderConfig.from_dict(program_config())
    rules = dict(decoder.param_rules(cfg))
    assert rules[r"attn/gate/kernel$"] == ("fsdp", "tp")
    assert reference.parameter_count(REF) == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(reference.init_params(jax.random.PRNGKey(0), REF)))


# ---- the data plane and the entry point --------------------------------------------------------------


def test_pipeline_counts_the_windowed_layers_blocks_beside_the_full_ones(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "examples", "transformer"))
    import transformer_spark as example
    from tensorflowonspark_tpu import tfrecord as tfr
    from tensorflowonspark_tpu.data import TextPipeline, Tokenizer

    data_dir = str(tmp_path / "corpus")
    example.make_text_corpus(data_dir, num_shards=2, records_per_shard=64)
    names = ["blocks_needed", "blocks_dense", "grid_steps", "pairs_visible", "pairs_in_blocks"]

    def value(name):
        return obs.snapshot()["counters"].get(name, {"value": 0})["value"]

    before = {name: value("flash_win_{}_total".format(name)) for name in names}
    full_before = value("flash_blocks_needed_total")
    pipe = TextPipeline(tfr.list_shards(data_dir), Tokenizer(kind="word", vocab_size=96), seq_len=1025, batch_size=2,
                        seed=3, epochs=6, prefetch_batches=64, attention_window=100)
    batches = list(pipe)
    assert len(batches) >= 2
    want = dict.fromkeys(names, 0)
    full = 0
    for batch in batches:
        attended = batch["segment_ids"][:, :-1]
        needed, dense, steps = flash_blocks.attended_blocks(attended, window=100)
        full += flash_blocks.attended_blocks(attended)[0]
        mask = flash_blocks.window_mask(attended, 100)
        for name, count in zip(names, (needed, dense, steps, int(mask.sum()), needed * 512 * 512)):
            want[name] += count
    assert {name: value("flash_win_{}_total".format(name)) - before[name] for name in names} == want
    assert value("flash_blocks_needed_total") - full_before == full >= want["blocks_needed"]
    assert 0 < want["pairs_visible"] < want["pairs_in_blocks"]
    with pytest.raises(ValueError, match="attention_window"):
        TextPipeline(tfr.list_shards(data_dir), Tokenizer(kind="word", vocab_size=95), seq_len=256, batch_size=2,
                     block_diffusion={"block_length": 4, "mask_id": 95}, attention_window=100)


def test_example_trains_the_toy_plan(tmp_path, capsys):
    """``transformer_spark.py --model decoder --model_config laguna_toy.json``: the layers' types are the
    configuration's, the example's ``main_fun`` builds pipeline, model and loss from it, and the loss falls."""
    sys.path.insert(0, os.path.join(ROOT, "examples", "transformer"))
    import transformer_spark as example

    data_dir = str(tmp_path / "corpus")
    example.make_text_corpus(data_dir, num_shards=2, records_per_shard=64)
    args = example.build_parser().parse_args([
        "--model", "decoder", "--model_config", "laguna_toy.json", "--data_dir", data_dir, "--seq_len", "128",
        "--batch_size", "8", "--train_steps", "4", "--log_steps", "1", "--tokenizer", "word", "--dtype", "float32",
        "--attention", "flash_interpret",
    ])
    args.model_cfg = TOY
    ctx = types.SimpleNamespace(
        initialize_distributed=lambda: None, num_processes=1, num_workers=1, executor_id=0, distributed=False)
    win_before = obs.snapshot()["counters"].get("flash_win_blocks_needed_total", {"value": 0})["value"]
    example.main_fun(args, ctx)
    out = capsys.readouterr().out
    assert "step 4: loss" in out and "transformer training complete" in out
    losses = [float(line.split("loss ")[1].split()[0]) for line in out.splitlines() if line.startswith("step ")]
    assert losses[-1] < losses[0]
    assert obs.snapshot()["counters"]["flash_win_blocks_needed_total"]["value"] > win_before
