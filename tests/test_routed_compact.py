"""The routed experts' compact slot buffer (``models/decoder.RoutedExperts``,
``ops/grouped_matmul.py``) on the CPU at toy widths: a chip that holds a
quarter of the experts runs them on ``C`` rows (twice the even share) and
falls back to all ``S = T * k`` on a step whose held slots do not fit. Both
are held, bit for bit, to the layer compiled on ``S`` rows alone, and to the
benchmark's dense references; the kernel that sums a token's held slots out
of the compact buffer (``ops/moe_combine.py``, interpreted) is held to the
``k`` gathers of ``[T, d]`` it replaced, kept here as its reference; the fallback is taken and counted; a model that
holds all its experts compiles no ``cond``; and inside the compact branch no
array as wide as the model or an expert has more rows than the buffer."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_testutil import REF, close, packed_batch, program_config, program_loss, tree_close
from benchmarks.reference import bd_lm, moe_lm
from tensorflowonspark_tpu.models import decoder
from tensorflowonspark_tpu.ops import grouped_matmul as gm
from tensorflowonspark_tpu.ops import moe_combine

ROWS, SEQ = 2, 256  # 512 tokens

#: sigmoid top-2 of 8 with a selection bias and a shared expert, 2 held: S = 1024, C = 512
SIGMOID = dict(REF, experts_held=[2, 2])
#: softmax top-4 of 16, no bias, no shared expert, 4 held: S = 2048, C = 1024
SOFTMAX = {"hidden_size": 32, "moe_intermediate_size": 16, "router_experts": 16, "experts_held": [4, 4],
           "num_experts_per_tok": 4}


#: how many of the slots the held experts get: under ``C``; a little over it (about 1.1 ``C``: the step a
#: router drifts into, its last share part held slots, part slots of experts held elsewhere); all of them
CROWDING = {"fits": None, "a_little_over": {"sigmoid": 0.25, "softmax": 0.32}, "all_held": {"sigmoid": 100.0, "softmax": 50.0}}


def _layer(scoring, crowding="fits", dtype="float32"):
    """``(cfg, params, x, plain)``: the program's configuration, one routed
    layer's seeded parameters, its input and the dense reference of it.
    ``crowding`` (a key of :data:`CROWDING`) leans the router towards the
    held experts until their slots do not fit in ``C``."""
    lean = CROWDING[crowding] and CROWDING[crowding][scoring]
    x = jax.random.normal(jax.random.PRNGKey(3), (ROWS, SEQ, 32), jnp.float32).astype(dtype)
    if scoring == "sigmoid":
        cfg = decoder.DecoderConfig.from_dict(program_config(SIGMOID, dtype=dtype))
        p = dict(moe_lm.init_params(jax.random.PRNGKey(7), SIGMOID)["layer_1"]["moe"])
        if lean:
            p["router_bias"] = jnp.zeros(8).at[jnp.array([2, 3])].set(lean)
        return cfg, p, x, lambda p, x: moe_lm.experts(x, p, SIGMOID)
    ref = dict(SOFTMAX, vocab_size=96, num_hidden_layers=1, num_attention_heads=8, num_key_value_heads=1, head_dim=8,
               rope_theta=1000000, rms_norm_eps=1e-6, block_length=4, mask_token_id=95)
    cfg = {k: v for k, v in ref.items() if k != "router_experts"}
    cfg.update(num_experts=16, norm_topk_prob=True, model_type="sdar_moe", objective="block_diffusion",
               attention="plain", dtype=dtype)
    cfg = decoder.DecoderConfig.from_dict(cfg)
    p = dict(bd_lm.init_params(jax.random.PRNGKey(7), ref)["layer_0"]["moe"])
    if lean:  # no bias to lean on: one constant input and the held experts' logits on it
        x = x.at[..., 0].set(3.0)
        p["router"] = p["router"].at[0, 4:8].set(lean)
    return cfg, p, x, lambda p, x: bd_lm.experts(x, p, ref)


def _value_and_grads(fn, p, x):
    """Output, counts, a loss and its gradient in every leaf and the input."""
    weigh = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def loss(p, x):
        y, counts = fn(p, x)
        return jnp.sum(y * weigh), (y, counts)

    (value, (y, counts)), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(p, x)
    return y, counts, value, grads


@pytest.mark.parametrize("crowding", list(CROWDING))
@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_compact_rows_equal_the_whole_buffer_and_the_reference(monkeypatch, scoring, crowding):
    """Bit for bit in bfloat16 on float32 parameters, as the configurations
    run, on either branch: the output, the loss and the input's gradient (in
    float32 the CPU's compiler contracts the weighing and the sum over ``k``
    into one rounding or two as it fuses them, either way in either program).
    The parameters' gradients are float32 sums over the buffer's rows, which
    the CPU's products block by the buffer's length (and the fallback adds up
    a share of the tokens at a time): equal to a few units in the last place.
    Against the dense reference in float32."""
    cfg, p, x, _ = _layer(scoring, crowding, "bfloat16")
    slots = ROWS * SEQ * cfg.num_experts_per_tok
    compact = gm.compact_rows(slots, cfg.held[1], cfg.n_routed_experts)
    assert compact == slots // 2

    def program(cfg):
        return lambda p, x: decoder.RoutedExperts(cfg).apply({"params": p}, x)

    y, counts, value, grads = _value_and_grads(program(cfg), p, x)
    assert y.dtype == jnp.bfloat16 and float(counts["slots_routed"]) == slots
    held = float(counts["slots_held"])
    assert {"fits": 0 < held <= compact, "a_little_over": compact < held < 1.25 * compact,
            "all_held": held == slots}[crowding]
    # nothing dropped: past C the layer fell back to all the slots, and says so
    assert (float(counts["layers_compact"]), float(counts["layers_at_bound"])) == ((1.0, 0.0) if held <= compact else (0.0, 1.0))

    with monkeypatch.context() as m:  # the same layer on S rows alone: no cond, no count of it
        m.setattr(gm, "compact_rows", lambda slots, held, experts: slots)
        whole_y, whole_counts, whole_value, whole_grads = _value_and_grads(program(cfg), p, x)
    assert "layers_compact" not in whole_counts
    np.testing.assert_array_equal(np.asarray(y), np.asarray(whole_y))
    assert float(value) == float(whole_value)
    np.testing.assert_array_equal(np.asarray(grads[1]), np.asarray(whole_grads[1]))
    for (path, got), want in zip(jax.tree_util.tree_flatten_with_path(grads[0])[0], jax.tree.leaves(whole_grads[0])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-6 * float(jnp.abs(want).max()),
                                   err_msg=jax.tree_util.keystr(path))

    cfg, p, x, plain = _layer(scoring, crowding)
    y, _, _, grads = _value_and_grads(program(cfg), p, x)
    want_y, _, _, want = _value_and_grads(lambda p, x: (plain(p, x), {}), p, x)
    close(y, want_y)
    tree_close(grads, want, 5e-4)


def _parents_layer(cfg, p, x, segment_ids):
    """``RoutedExperts`` as it stood before PR 43, on all ``S`` rows: the
    chosen scores by ``take_along_axis``, the way back to slot order by
    ``slot_places``' scatter, every gather XLA's own with its own transpose
    (the dispatch's in float32, as the layer's gradient sums a token's slots)."""
    dt, k, (first, held) = x.dtype, cfg.num_experts_per_tok, cfg.held
    flat = x.reshape(-1, x.shape[-1])
    logits = jnp.dot(flat.astype(jnp.float32), p["router"], precision=jax.lax.Precision.HIGHEST)
    if cfg.scoring_func == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(scores, k)
    else:
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(p["router_bias"]), k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor
    if not cfg.padding_slots:
        chosen = jnp.where(segment_ids.reshape(-1, 1) > 0, chosen, cfg.n_routed_experts)
    order, sizes, _ = gm.sort_slots(chosen.reshape(-1), first, held)
    place = gm.slot_places(order)
    sorted_in = flat.astype(jnp.float32)[order // k].astype(dt)
    hidden = jax.nn.silu(gm.grouped_matmul(sorted_in, p["experts_gate"].astype(dt), sizes)) * gm.grouped_matmul(
        sorted_in, p["experts_up"].astype(dt), sizes)
    sorted_out = gm.grouped_matmul(hidden, p["experts_down"].astype(dt), sizes)
    weighted = (sorted_out.astype(jnp.float32) * weights.reshape(-1)[order][:, None]).astype(dt)
    routed = jnp.sum(weighted[place].reshape(-1, k, flat.shape[-1]), axis=1, dtype=jnp.float32).astype(dt)
    shared = decoder.SwiGLU(cfg, cfg.shared_width).apply({"params": p["shared"]}, flat) if cfg.shared_width else 0
    return (routed + shared).reshape(x.shape)


@pytest.mark.parametrize("padding_slots", [True, False], ids=["padding_routed", "padding_routed_nowhere"])
@pytest.mark.parametrize("crowding", list(CROWDING))
@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_layer_is_what_it_was_with_a_gather_and_a_scatter(scoring, crowding, padding_slots):
    """On either branch, with padding positions in the batch routed like any
    other or nowhere. The chosen scores are the parent's bit for bit, and in
    float32 the layer's result, loss and every gradient are the parent's to
    float32 rounding: XLA adds the chosen scores up for the weights' divisor
    in another order once they are a reduction and not a gather (a float32
    unit), and the kernel sums a token's slots in held-expert order. In
    bfloat16 on float32 parameters, as the configurations run, such a unit
    shows in the result where it crosses a bfloat16 rounding, seldom, and
    then as one step."""
    import dataclasses

    segment_ids = jnp.ones((ROWS, SEQ), jnp.int32).at[:, SEQ - 40:].set(0)

    def both(dtype):
        cfg, p, x, _ = _layer(scoring, crowding, dtype)
        cfg = dataclasses.replace(cfg, padding_slots=padding_slots)
        got = _value_and_grads(lambda p, x: decoder.RoutedExperts(cfg).apply({"params": p}, x, segment_ids), p, x)
        return got, _value_and_grads(lambda p, x: (_parents_layer(cfg, p, x, segment_ids), {}), p, x)

    (y, counts, value, grads), (want_y, _, want_value, want) = both("float32")
    assert float(counts["layers_at_bound"]) == float(counts["slots_held"] > counts["slots_routed"] / 2)  # C = S / 2
    assert float(counts["layers_at_bound"]) == float(crowding != "fits") or not padding_slots
    close(y, want_y, 2e-6)
    close(value, want_value, 2e-6)
    tree_close(grads, want, 1e-5)  # sums of hundreds of float32 terms, against the largest entry
    (y, _, _, _), (want_y, _, _, _) = both("bfloat16")
    y, want_y = np.asarray(y, np.float32), np.asarray(want_y, np.float32)
    assert np.mean(y != want_y) < 1e-3
    np.testing.assert_allclose(y, want_y, rtol=2.0 ** -7, atol=1e-6)


def test_the_chosen_scores_are_a_masked_sum_and_not_a_gather():
    """``decoder._scores_at`` against ``take_along_axis``: the values bit for
    bit, the scores' gradient too (one term a ``(t, e)``), and neither a
    gather nor a scatter in the jaxpr of value and gradient, where
    ``take_along_axis`` has both and ``top_k``'s own values a scatter-add."""
    tokens, experts, k = 512, 16, 4
    scores = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(1), (tokens, experts)) * 3.0, axis=-1)
    _, chosen = jax.lax.top_k(scores, k)
    weigh = jax.random.normal(jax.random.PRNGKey(2), (tokens, k))

    def value_and_grad(read):
        return jax.value_and_grad(lambda s: jnp.sum(read(s) * weigh))

    got, d_got = jax.jit(value_and_grad(lambda s: decoder._scores_at(s, chosen)))(scores)
    want, d_want = jax.jit(value_and_grad(lambda s: jnp.take_along_axis(s, chosen, axis=-1)))(scores)
    assert float(got) == float(want)
    np.testing.assert_array_equal(np.asarray(d_got), np.asarray(d_want))
    np.testing.assert_array_equal(
        np.asarray(decoder._scores_at(scores, chosen)), np.asarray(jnp.take_along_axis(scores, chosen, axis=-1)))
    walks = {"gather", "scatter", "scatter-add"}
    assert not _primitives(jax.make_jaxpr(value_and_grad(lambda s: decoder._scores_at(s, chosen)))(scores).jaxpr) & walks
    assert {"gather", "scatter-add"} <= _primitives(
        jax.make_jaxpr(value_and_grad(lambda s: jnp.take_along_axis(s, chosen, axis=-1)))(scores).jaxpr)
    assert "scatter-add" in _primitives(jax.make_jaxpr(value_and_grad(lambda s: jax.lax.top_k(s, k)[0]))(scores).jaxpr)


def _k_gathers(buffer, place, k):
    """What ``gm._sum_over_slots`` was on a buffer shorter than the slots
    before PR 42, and is held to now: ``float32 [T, d]``, each token's ``k``
    slots found in ``buffer`` by their ``place`` there and summed in slot
    order, a slot that is not in the buffer (its place is past the end)
    finding a zero row behind it."""
    rows = buffer.shape[0]
    padded = jnp.concatenate([buffer, jnp.zeros((1,) + buffer.shape[1:], buffer.dtype)])
    at = jnp.minimum(place, rows).reshape(-1, k)
    total = padded[at[:, 0]].astype(jnp.float32)
    for j in range(1, k):
        total = total + padded[at[:, j]].astype(jnp.float32)
    return total


#: 32 experts of which this chip holds 4, 5, 6 and 7: ``C = T * k / 4``; the kernel's tile is 512 tokens
EXPERTS, FIRST, HELD, WIDE = 32, 4, 4, 128
#: kind of routing: tokens, experts a token
ROUTINGS = {
    "even": (1024, 8), "no_held_slot_in_a_tile": (1536, 8), "one_expert_holds_a_tiles_slots": (1024, 10),
    "held_slots_exactly_the_buffer": (1024, 4), "padding_routed_nowhere": (1024, 10),
    "tokens_no_multiple_of_the_tile": (1300, 4),
}


def _routing(kind):
    """``int32 [T, k]``: the experts each token chose."""
    tokens, k = ROUTINGS[kind]
    _, chosen = jax.lax.top_k(jax.random.normal(jax.random.PRNGKey(5), (tokens, EXPERTS)), k)
    token = jnp.arange(tokens)[:, None]
    elsewhere = FIRST + HELD + jnp.arange(k)[None, :]  # k experts held on other chips
    if kind == "no_held_slot_in_a_tile":  # the second tile's tokens
        chosen = jnp.where((token >= 512) & (token < 1024), elsewhere, chosen)
    if kind == "one_expert_holds_a_tiles_slots":  # every token of the first tile: 512 rows of one expert, four windows and more
        chosen = jnp.where(token < 512, elsewhere.at[0, 0].set(FIRST), chosen)
    if kind == "held_slots_exactly_the_buffer":  # one held slot a token, T = C of them
        chosen = elsewhere.at[0, 0].set(0) + jnp.where(jnp.arange(k)[None, :] == 0, FIRST + token % HELD, 0)
    if kind == "padding_routed_nowhere":  # the row's last 300 positions, as ``RoutedExperts`` routes segment id 0
        chosen = jnp.where(token >= tokens - 300, EXPERTS, chosen)
    return chosen.astype(jnp.int32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", list(ROUTINGS))
def test_the_kernel_sums_what_the_k_gathers_summed(kind, dtype):
    """Values and both custom VJPs (``slots_to_tokens`` forward, whose
    gradient is a gather; ``rows_to_slots``, whose gradient is the kernel)
    against the plain form and ``jax``'s own transposes of it in float32. The
    kernel adds a token's float32 terms in held-expert order where the
    gathers added them in slot order: equal to float32 rounding of the sum,
    which in bfloat16 is at most one step of the result."""
    tokens, k = ROUTINGS[kind]
    chosen = _routing(kind)
    order, sizes, _ = gm.sort_slots(chosen.reshape(-1), FIRST, HELD)
    place, used = gm.slot_places(order), int(jnp.sum(sizes))
    compact = gm.compact_rows(tokens * k, HELD, EXPERTS)
    assert compact < tokens * k and 0 < used <= compact and compact % moe_combine.WINDOW == 0
    head = order[:compact]
    # as ``gm.grouped_matmul`` leaves them: zeros past the last group, values and gradients
    live = (jnp.arange(compact) < used)[:, None]
    buffer = jnp.where(live, jax.random.normal(jax.random.PRNGKey(6), (compact, WIDE)), 0).astype(dtype)
    d_buffer = jnp.where(live, jax.random.normal(jax.random.PRNGKey(7), (compact, WIDE)), 0).astype(dtype)
    rows = jax.random.normal(jax.random.PRNGKey(8), (tokens, WIDE)).astype(dtype)
    d_rows = jax.random.normal(jax.random.PRNGKey(9), (tokens, WIDE)).astype(dtype)
    step = {"float32": 1e-6, "bfloat16": 2.0 ** -8}[dtype]

    def held_to(got, want):
        assert got.dtype == jnp.dtype(dtype)
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), rtol=step, atol=1e-5)

    tile = moe_combine.token_tile(tokens)
    tile_of_step, window, first, last, steps = map(np.asarray, moe_combine.work_list(head // k, sizes, tokens, tile))
    assert tile == 512 and -(-tokens // tile) <= steps[0] <= len(tile_of_step) and np.all(np.diff(tile_of_step) >= 0)
    ranges = set(zip(first[:steps[0]].tolist(), last[:steps[0]].tolist()))  # a range has a step a window it touches
    assert sum(end - start for start, end in ranges) == used
    if kind == "no_held_slot_in_a_tile":  # one step for the tile, its range empty
        (start, end), = [(first[s], last[s]) for s in range(steps[0]) if tile_of_step[s] == 1]
        assert start == end
    if kind == "one_expert_holds_a_tiles_slots":  # the first expert's 512 rows of the first tile, window after window
        assert list(window[:4]) == [0, 1, 2, 3] and (first[0], last[0]) == (0, 512) and int(sizes[0]) > 512
    if kind == "held_slots_exactly_the_buffer":
        assert used == compact == tokens
    if kind == "tokens_no_multiple_of_the_tile":
        assert tokens % tile and tile_of_step[steps[0] - 1] == tokens // tile

    got, pull = jax.vjp(lambda b: gm.slots_to_tokens(b, order, sizes, k), buffer)
    want, plain_pull = jax.vjp(lambda b: _k_gathers(b, place, k), buffer.astype(jnp.float32))
    held_to(got, want)
    held_to(pull(d_rows)[0], plain_pull(d_rows.astype(jnp.float32))[0])
    if kind == "no_held_slot_in_a_tile":
        assert float(jnp.abs(got[512:1024]).max()) == 0.0 and float(jnp.abs(got[:512]).max()) > 0.0
    if kind == "padding_routed_nowhere":
        assert float(jnp.abs(got[tokens - 300:]).max()) == 0.0

    got, pull = jax.vjp(lambda r: gm.rows_to_slots(r, order, sizes, compact, k), rows)
    want, plain_pull = jax.vjp(lambda r: r[head // k], rows.astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want))
    held_to(pull(d_buffer)[0], plain_pull(d_buffer.astype(jnp.float32))[0])
    # the same sum by the other route: the gathers on the cotangent
    held_to(pull(d_buffer)[0], _k_gathers(d_buffer, place, k))


def test_the_kernels_list_of_steps_stays_under_its_bound_whatever_the_routing():
    """Every slot to one held expert, then spread as unevenly as a sort
    allows: the steps the device counts never pass ``C / 128 + tiles *
    held``, the grid's static length; and the rows they fetch are counted."""
    tokens, k, held, tile = 2048, 2, 4, 512
    rows = 1024
    for sizes in ([1024, 0, 0, 0], [0, 0, 0, 1024], [1, 511, 129, 383], [127, 129, 1, 0], [0, 0, 0, 0]):
        sizes = jnp.array(sizes, jnp.int32)
        # each group's tokens ascending, as a stable sort leaves them, and as far apart as they can be
        token_of_row = jnp.concatenate([
            jnp.linspace(0, tokens - 1, int(n)).astype(jnp.int32) for n in sizes]
            + [jnp.zeros(rows - int(sizes.sum()), jnp.int32)])
        tile_of_step, window, first, last, steps = map(np.asarray, moe_combine.work_list(token_of_row, sizes, tokens, tile))
        assert len(tile_of_step) == rows // moe_combine.WINDOW + tokens // tile * held
        assert tokens // tile <= steps[0] <= len(tile_of_step)
        assert np.all(window >= 0) and np.all(window < rows // moe_combine.WINDOW)
        fetched = float(moe_combine.rows_fetched(token_of_row, sizes, tokens))
        assert fetched == steps[0] * moe_combine.WINDOW >= int(sizes.sum())
        buffer = jnp.where((jnp.arange(rows) < sizes.sum())[:, None], 1.0, 7.0) * jnp.ones((rows, WIDE), jnp.bfloat16)
        out = moe_combine.combine(buffer, token_of_row, sizes, tokens=tokens, interpret=True)
        want = np.bincount(np.asarray(token_of_row)[:int(sizes.sum())], minlength=tokens)
        np.testing.assert_array_equal(np.asarray(out[:, 0], np.float32), want)


def test_the_rule_for_the_buffers_length():
    assert gm.compact_rows(131072, 16, 128) == 32768  # sdar-30b-a3b.bd4-packed4k: a quarter
    assert gm.compact_rows(32768, 8, 64) == 8192  # xing4-a4b.packed8k
    assert gm.compact_rows(1000, 1, 8) == 512  # up to the tile
    assert gm.compact_rows(192, 3, 8) == 192  # the tile is longer than the bound
    assert gm.compact_rows(4096, 4, 8) == gm.compact_rows(4096, 8, 8) == 4096  # half or more held: the bound
    assert list(inspect.signature(gm.grouped_matmul).parameters) == ["lhs", "rhs", "group_sizes"]


def _eqns(jaxpr, fallbacks=True):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold, a
    kernel's body apart (its ``pl.when`` and loops are not the layer's);
    without ``fallbacks``, of a ``cond`` the compact branch alone (``cond(fits,
    compact, fallback)``: index 1 is the true branch)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "cond" and not fallbacks:
            yield from _eqns(eqn.params["branches"][1].jaxpr, fallbacks)
        elif eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _eqns(sub, fallbacks)


def _primitives(jaxpr):
    return {eqn.primitive.name for eqn in _eqns(jaxpr)}


def _arrays(jaxpr):
    """``(primitive, shape)`` of every array an equation of ``jaxpr`` defines."""
    return [(eqn.primitive.name, tuple(v.aval.shape)) for eqn in _eqns(jaxpr) for v in eqn.outvars
            if hasattr(v.aval, "shape")]


def test_all_experts_held_compiles_no_cond():
    whole = dict(SIGMOID, experts_held=[0, 8])
    cfg = decoder.DecoderConfig.from_dict(program_config(whole))
    p = moe_lm.init_params(jax.random.PRNGKey(7), whole)["layer_1"]["moe"]
    x = jnp.zeros((ROWS, SEQ, 32), jnp.float32)

    def grads(p, x):
        return jax.grad(lambda p, x: jnp.sum(decoder.RoutedExperts(cfg).apply({"params": p}, x)[0]), argnums=(0, 1))(p, x)

    names = _primitives(jax.make_jaxpr(grads)(p, x).jaxpr)
    assert "ragged_dot_general" in names or "ragged_dot" in names
    assert not names & {"cond", "concatenate"}  # neither the fallback nor the zero row behind a shorter buffer
    cfg = decoder.DecoderConfig.from_dict(program_config(SIGMOID))
    p = moe_lm.init_params(jax.random.PRNGKey(7), SIGMOID)["layer_1"]["moe"]
    assert "cond" in _primitives(jax.make_jaxpr(grads)(p, x).jaxpr)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("recomputed", [False, True], ids=["kept", "recomputed"])
def test_the_compact_branch_holds_no_array_of_the_bounds_length(recomputed, dtype):
    cfg, p, x, _ = _layer("sigmoid", "fits", dtype)
    tokens, slots, compact = ROWS * SEQ, ROWS * SEQ * 2, ROWS * SEQ
    wide = {cfg.hidden_size, cfg.moe_intermediate_size}

    def loss(p, x):
        return jnp.sum(decoder.RoutedExperts(cfg).apply({"params": p}, x)[0])

    def long_and_wide(jaxpr):
        return [(name, shape) for name, shape in _arrays(jaxpr)
                if len(shape) >= 2 and shape[-1] in wide and shape[0] > max(compact + 1, tokens)]

    jaxpr = jax.make_jaxpr(jax.grad(jax.checkpoint(loss) if recomputed else loss, argnums=(0, 1)))(p, x).jaxpr
    conds = [eqn for eqn in _eqns(jaxpr) if eqn.primitive.name == "cond"]
    # the forward pass, and the backward pass with the forward pass again inside it: recomputed or not, the layer
    # keeps nothing of a branch (this loss does not read the layer's result again, so nothing else runs it)
    assert len(conds) == 2
    for cond in conds:
        fallback, branch = cond.params["branches"]  # cond(fits, compact, fallback): index 1 is the true branch
        # the way back to token order is the kernel on the buffer's rows: never [S, d] or [T, k, d], no zero row
        # behind the buffer, and no gather but the dispatch's (and its gradient's) [C, d] from [T, d]
        assert long_and_wide(branch.jaxpr) == [] and not _primitives(branch.jaxpr) & {"while", "scan", "concatenate"}
        kernels = [eqn for eqn in _eqns(branch.jaxpr) if eqn.primitive.name == "pallas_call"]
        assert kernels and all(eqn.params["name"] == "moe_combine" for eqn in kernels)
        assert {eqn.outvars[0].aval.shape for eqn in kernels} == {(tokens, cfg.hidden_size)}
        gathered = [eqn for eqn in _eqns(branch.jaxpr) if eqn.primitive.name == "gather" and len(eqn.outvars[0].aval.shape) == 2]
        assert gathered and all(eqn.outvars[0].aval.shape == (compact, cfg.hidden_size) for eqn in gathered)
        # the fallback: a share of the tokens at a time, a share's slots no more than the compact buffer's rows
        assert long_and_wide(fallback.jaxpr) == [] and "scan" in _primitives(fallback.jaxpr)
        # the products sit in no call (jnp's own small ones apart): XLA hands a call's name to what it inlines, and
        # their kernels are found by their bare one (benchmarks/layer_metrics/_moe.is_grouped_product)
        for inside in (branch, fallback):
            called = {eqn.primitive.name for call in _eqns(inside.jaxpr) if call.primitive.name in ("jit", "pjit")
                      for sub in jax.core.jaxprs_in_params(call.params) for eqn in _eqns(sub)}
            assert called and not any(name.startswith("ragged_dot") for name in called)
    # the router's bookkeeping, anywhere but in the fallback: the chosen scores are read by no gather of [T, E] (and
    # handed their gradient by no scatter-add), the order's inverse is not computed (no int32 scatter); the one
    # scatter-add left is the transpose of the weights' gather, float32 [T * k]
    outside = list(_eqns(jaxpr, fallbacks=False))
    assert len([eqn for eqn in outside if eqn.primitive.name == "cond"]) == 2
    scatters = [eqn.outvars[0].aval for eqn in outside if eqn.primitive.name.startswith("scatter")]
    assert scatters and all((aval.shape, aval.dtype) == ((slots,), jnp.float32) for aval in scatters)
    assert not [eqn for eqn in outside if eqn.primitive.name == "gather"
                and eqn.invars[0].aval.shape == (tokens, cfg.n_routed_experts)]
    assert any(eqn.primitive.name.startswith("scatter") and eqn.outvars[0].aval.dtype == jnp.int32
               for eqn in _eqns(jaxpr))  # the fallback's shares each compute their own


def test_the_model_carries_the_two_counts_out_of_the_step():
    batch = packed_batch(rows=ROWS, seq=SEQ)
    model, loss_fn = program_loss(SIGMOID)
    variables = {"params": moe_lm.init_params(jax.random.PRNGKey(7), SIGMOID)}
    _, metrics = jax.jit(loss_fn)(variables["params"], batch)
    assert float(metrics["counter/moe_layers_compact"]) + float(metrics["counter/moe_layers_at_bound"]) == 2  # routed layers
    assert float(metrics["counter/moe_layers_compact"]) == 2
    # what the way back to token order fetched: whole windows, no fewer rows than the held slots
    fetched, held = float(metrics["counter/moe_combine_rows_fetched"]), float(metrics["counter/moe_slots_held"])
    assert fetched % moe_combine.WINDOW == 0 and held <= fetched <= 2 * ROWS * SEQ + 2 * 2 * moe_combine.WINDOW
    _, metrics = jax.jit(program_loss(REF)[1])(moe_lm.init_params(jax.random.PRNGKey(7), REF), packed_batch())
    assert "counter/moe_slots_held" in metrics and "counter/moe_layers_compact" not in metrics  # C == S: no cond to count
    assert "counter/moe_combine_rows_fetched" not in metrics  # and no kernel: one gather back to slot order


@pytest.mark.parametrize("told", [False, True], ids=["mesh_not_told", "mesh_told"])
@pytest.mark.parametrize("crowding", ["fits", "all_held"])
def test_a_batch_sharded_over_a_mesh_gives_the_one_device_layer(crowding, told):
    """dp 2 x tp 2 on the CPU's virtual devices: the ``cond`` and its backward
    pass partition as the rest of the layer does, on either branch. A layer
    that is told its mesh (as ``DecoderLayer`` tells it) runs the kernel under
    a ``shard_map``, which a Mosaic call needs on more than one chip; one that
    is not leaves the interpreted kernel to the partitioner."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg, p, x, _ = _layer("sigmoid", crowding)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))

    def program(p, x, mesh=None):
        return decoder.RoutedExperts(cfg, mesh).apply({"params": p}, x)

    want_y, _, _, want = _value_and_grads(program, p, x)
    if told:
        primitives = [_primitives(jax.make_jaxpr(lambda p, x: program(p, x, m)[0])(p, x).jaxpr) for m in (mesh, None)]
        assert "shard_map" in primitives[0] and "shard_map" not in primitives[1]
    with mesh:
        y, counts, _, grads = _value_and_grads(
            lambda p, x: program(p, x, mesh if told else None), p, jax.device_put(x, NamedSharding(mesh, P("dp", None, None))))
    assert float(counts["layers_at_bound"]) == float(crowding == "all_held")
    close(y, want_y)
    tree_close(grads, want, 5e-4)


def test_on_a_mesh_the_kernel_takes_the_columns_a_tp_shard_at_a_time():
    """256 columns over ``tp`` 2 are whole lanes a shard: each chip sums its
    128 columns of every token, the other operands whole; 32 columns are not,
    and stay whole. Either way what one device gives, bit for bit."""
    from jax.sharding import Mesh

    tokens, k = ROUTINGS["even"]
    order, sizes, _ = gm.sort_slots(_routing("even").reshape(-1), FIRST, HELD)
    compact = gm.compact_rows(tokens * k, HELD, EXPERTS)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    for wide, split in ((256, True), (32, False)):
        buffer = jnp.where((jnp.arange(compact) < jnp.sum(sizes))[:, None],
                           jax.random.normal(jax.random.PRNGKey(6), (compact, wide)), 0).astype(jnp.bfloat16)
        want = gm.slots_to_tokens(buffer, order, sizes, k)
        on_mesh = jax.jit(lambda b: gm.slots_to_tokens(b, order, sizes, k, mesh))
        np.testing.assert_array_equal(np.asarray(on_mesh(buffer), np.float32), np.asarray(want, np.float32))
        kernels = [eqn for eqn in _eqns(jax.make_jaxpr(on_mesh)(buffer).jaxpr) if eqn.primitive.name == "pallas_call"]
        assert [eqn.outvars[0].aval.shape for eqn in kernels] == [(tokens, wide // 2 if split else wide)]
