"""The routed experts' compact slot buffer (``models/decoder.RoutedExperts``,
``ops/grouped_matmul.py``) on the CPU at toy widths: a chip that holds a
quarter of the experts runs them on ``C`` rows (twice the even share) and
falls back to all ``S = T * k`` on a step whose held slots do not fit. Both
are held, bit for bit, to the layer compiled on ``S`` rows alone, and to the
benchmark's dense references; the fallback is taken and counted; a model that
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


def test_the_rule_for_the_buffers_length():
    assert gm.compact_rows(131072, 16, 128) == 32768  # sdar-30b-a3b.bd4-packed4k: a quarter
    assert gm.compact_rows(32768, 8, 64) == 8192  # xing4-a4b.packed8k
    assert gm.compact_rows(1000, 1, 8) == 512  # up to the tile
    assert gm.compact_rows(192, 3, 8) == 192  # the tile is longer than the bound
    assert gm.compact_rows(4096, 4, 8) == gm.compact_rows(4096, 8, 8) == 4096  # half or more held: the bound
    assert list(inspect.signature(gm.grouped_matmul).parameters) == ["lhs", "rhs", "group_sizes"]


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


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
        # the way back to token order is k gathers of [T, d], never [S, d] or [T, k, d]
        assert long_and_wide(branch.jaxpr) == [] and not _primitives(branch.jaxpr) & {"while", "scan"}
        assert sum(name == "gather" and shape == (tokens, cfg.hidden_size) for name, shape in _arrays(branch.jaxpr)) >= 2
        # the fallback: a share of the tokens at a time, a share's slots no more than the compact buffer's rows
        assert long_and_wide(fallback.jaxpr) == [] and "scan" in _primitives(fallback.jaxpr)
        # the products sit in no call (jnp's own small ones apart): XLA hands a call's name to what it inlines, and
        # their kernels are found by their bare one (benchmarks/layer_metrics/_moe.is_grouped_product)
        for inside in (branch, fallback):
            called = {eqn.primitive.name for call in _eqns(inside.jaxpr) if call.primitive.name in ("jit", "pjit")
                      for sub in jax.core.jaxprs_in_params(call.params) for eqn in _eqns(sub)}
            assert called and not any(name.startswith("ragged_dot") for name in called)


def test_the_model_carries_the_two_counts_out_of_the_step():
    batch = packed_batch(rows=ROWS, seq=SEQ)
    model, loss_fn = program_loss(SIGMOID)
    variables = {"params": moe_lm.init_params(jax.random.PRNGKey(7), SIGMOID)}
    _, metrics = jax.jit(loss_fn)(variables["params"], batch)
    assert float(metrics["counter/moe_layers_compact"]) + float(metrics["counter/moe_layers_at_bound"]) == 2  # routed layers
    assert float(metrics["counter/moe_layers_compact"]) == 2
    _, metrics = jax.jit(program_loss(REF)[1])(moe_lm.init_params(jax.random.PRNGKey(7), REF), packed_batch())
    assert "counter/moe_slots_held" in metrics and "counter/moe_layers_compact" not in metrics  # C == S: no cond to count


@pytest.mark.parametrize("crowding", ["fits", "all_held"])
def test_a_batch_sharded_over_a_mesh_gives_the_one_device_layer(crowding):
    """dp 2 x tp 2 on the CPU's virtual devices: the ``cond`` and its backward
    pass partition as the rest of the layer does, on either branch."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg, p, x, _ = _layer("sigmoid", crowding)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))

    def program(p, x):
        return decoder.RoutedExperts(cfg).apply({"params": p}, x)

    want_y, _, _, want = _value_and_grads(program, p, x)
    with mesh:
        y, counts, _, grads = _value_and_grads(program, p, jax.device_put(x, NamedSharding(mesh, P("dp", None, None))))
    assert float(counts["layers_at_bound"]) == float(crowding == "all_held")
    close(y, want_y)
    tree_close(grads, want, 5e-4)
