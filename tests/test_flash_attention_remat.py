"""What a recomputed layer keeps (``flash_attention.REMAT_POLICY``): of its
flash call the output and the log-sum-exp, so the gradient of a model of ``L``
recomputed layers runs the forward kernel ``L`` times and not ``2 L``; of its
attention sub-layer the projections' results (under latent attention the two
latents) and the sub-layer's own, so the recomputed pass multiplies by none of
their matrices; and it computes what it computed before, bit for bit. The
kernels run in the Pallas interpreter."""

import collections
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_testutil import REF, ROOT, packed_batch, program_config
from tensorflowonspark_tpu.data import text_plane
from tensorflowonspark_tpu.models import decoder, get_model, transformer
from tensorflowonspark_tpu.ops import flash_attention as fa

LAYERS = 2
#: the decoder's toy configuration, cut to a dense and a routed layer
TOY = dict(REF, num_hidden_layers=LAYERS, hc_sinkhorn_iters=4)


def _model(family, remat):
    if family == "decoder":
        return get_model("decoder", **program_config(TOY, attention="flash_interpret", remat=remat))
    return get_model(
        "transformer", vocab_size=TOY["vocab_size"], d_model=32, n_layers=LAYERS, n_heads=2, d_ff=64,
        max_seq_len=64, attention="flash_interpret", remat=remat)


def _case(family, segmented, remat=True):
    """``(gradient function, parameters, batch)`` of the family's toy model."""
    model = _model(family, remat)
    batch = packed_batch()
    if not segmented:
        batch = {"tokens": batch["tokens"]}
    params = model.init(jax.random.PRNGKey(3), batch["tokens"][:, :-1])["params"]
    loss_fn = transformer.make_loss_fn(model)
    return jax.value_and_grad(lambda p, b: loss_fn(p, b)[0]), params, batch


def _primitives(jaxpr, found=None):
    """Every equation of ``jaxpr`` and of the jaxprs inside its equations, with
    multiplicity: ``{primitive or kernel name: count}``."""
    found = collections.Counter() if found is None else found
    holds_equations = lambda v: hasattr(getattr(v, "jaxpr", v), "eqns")  # noqa: E731  (a jaxpr, open or closed)
    for eqn in jaxpr.eqns:
        found[eqn.params["name"] if eqn.primitive.name == "pallas_call" else eqn.primitive.name] += 1
        for value in jax.tree.leaves(eqn.params, is_leaf=holds_equations):
            if holds_equations(value):
                _primitives(getattr(value, "jaxpr", value), found)
    return found


def _kernels(found):
    return {name: count for name, count in found.items() if name.startswith("flash_")}


def _flash_calls(grad, params, batch):
    return _kernels(_primitives(jax.make_jaxpr(grad)(params, batch).jaxpr))


def _same_numbers(loss, grads, want_loss, want):
    assert np.asarray(loss) == np.asarray(want_loss) and np.isfinite(loss)
    flat, flat_want = jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree.leaves(want)
    assert len(flat) == len(flat_want)
    for (path, got), leaf in zip(flat, flat_want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(leaf), err_msg=jax.tree_util.keystr(path))
    assert any(np.asarray(leaf).any() for leaf in flat_want)


def _with_policy(monkeypatch, policy):
    for module in (transformer, decoder):
        monkeypatch.setattr(module, "REMAT_POLICY", policy)


def _without_policy(monkeypatch):
    _with_policy(monkeypatch, None)


#: the policy before the attention sub-layer's products were kept
_FLASH_ALONE = jax.checkpoint_policies.save_only_these_names(fa.KEPT_O, fa.KEPT_LSE)


@pytest.mark.parametrize("segmented", [True, False], ids=["segmented", "unsegmented"])
@pytest.mark.parametrize("family", ["transformer", "decoder"])
def test_recomputed_layers_run_the_forward_kernel_once(family, segmented, monkeypatch):
    suffix = "_seg" if segmented else ""
    grad, params, batch = _case(family, segmented)
    assert _flash_calls(grad, params, batch) == {"flash_fwd" + suffix: LAYERS, "flash_bwd_dkv" + suffix: LAYERS}
    loss, grads = jax.jit(grad)(params, batch)

    _without_policy(monkeypatch)
    grad, _, _ = _case(family, segmented)
    assert _flash_calls(grad, params, batch) == {"flash_fwd" + suffix: 2 * LAYERS, "flash_bwd_dkv" + suffix: LAYERS}
    want_loss, want = jax.jit(grad)(params, batch)
    _same_numbers(loss, grads, want_loss, want)


with open(os.path.join(ROOT, "examples", "transformer", "laguna_toy.json")) as _f:
    _LAGUNA = json.load(_f)
#: ``gqa`` at toy widths: 4 query heads a key/value head, top-2 of 8 experts of which 4 are held
_GQA = {
    "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": LAYERS, "num_attention_heads": 4, "num_key_value_heads": 1,
    "head_dim": 8, "rope_theta": 1000000, "moe_intermediate_size": 16, "num_experts": 8, "experts_held": [2, 4],
    "num_experts_per_tok": 2, "norm_topk_prob": True, "rms_norm_eps": 1e-6, "model_type": "sdar_moe",
}
#: attention kind: (the decoder's configuration or None for ``models/transformer``, the attention products a layer
#: whose results a recomputed layer keeps, the kernels of a gradient ``{name: calls}``)
KINDS = {
    # q, k, v, o
    "transformer": (None, 4, {"flash_fwd_seg": LAYERS, "flash_bwd_dkv_seg": LAYERS}),
    # q_a, kv_a, o: the up-projections q_b and kv_b run again from the latents
    "mla": (program_config(TOY), 3, {"flash_fwd_seg": LAYERS, "flash_bwd_dkv_seg": LAYERS}),
    # q, k, v, o, named before the head norms
    "gqa_qk_norm": (dict(_GQA, qk_norm=True), 4, {"flash_fwd_seg": LAYERS, "flash_bwd_dkv_seg": LAYERS}),
    # the example's toy plan narrowed: two full layers round three windowed ones, a gate a head (its product stays)
    "gqa_window_gate": (
        dict(_LAGUNA, vocab_size=96, hidden_size=32, intermediate_size=80, head_dim=8, moe_intermediate_size=16,
             shared_expert_intermediate_size=24),
        4, {"flash_fwd_seg": 2, "flash_bwd_dkv_seg": 2, "flash_fwd_win": 3, "flash_bwd_dkv_win": 3}),
    "block_diffusion": (
        dict(_GQA, objective="block_diffusion", block_length=4, mask_token_id=95),
        4, {"flash_fwd_bd": LAYERS, "flash_bwd_dkv_bd": LAYERS}),
}


def _kind(kind, remat=True):
    """``(gradient function, parameters, batch, kept products a layer, kernel calls)``
    of the attention kind's toy model."""
    cfg, products, kernels = KINDS[kind]
    if cfg is None:
        return _case("transformer", True, remat) + (products, kernels)
    model = get_model("decoder", **dict(cfg, attention="flash_interpret", dtype="float32", remat=remat))
    batch = packed_batch()
    if cfg.get("objective") == "block_diffusion":
        tokens, seg, pos = (np.asarray(batch[k])[:, :-1] for k in ("tokens", "segment_ids", "positions"))
        noised, weights = text_plane.noise_blocks(
            tokens, seg, pos, cfg["block_length"], cfg["mask_token_id"], 0.05, np.random.default_rng(5))
        batch = {"tokens": tokens, "noised_tokens": noised, "loss_weights": weights, "segment_ids": seg, "positions": pos}
    params = model.init(jax.random.PRNGKey(3), jnp.asarray(batch["tokens"])[:, :-1])["params"]
    loss_fn = transformer.make_loss_fn(model)
    return jax.value_and_grad(lambda p, b: loss_fn(p, b)[0]), params, batch, products, kernels


@pytest.mark.parametrize("kind", list(KINDS))
def test_recomputed_layers_run_no_attention_projection(kind, monkeypatch):
    """Against the policy that kept the flash call's two results alone, the
    gradient holds as many ``dot_general``s fewer as the kind has attention
    products whose results are kept now, the kernels are called as often
    (once each way a layer), and against no policy at all every number is the
    same."""
    grad, params, batch, products, kernels = _kind(kind)
    found = _primitives(jax.make_jaxpr(grad)(params, batch).jaxpr)
    assert _kernels(found) == kernels
    loss, grads = jax.jit(grad)(params, batch)

    _with_policy(monkeypatch, _FLASH_ALONE)
    before = _primitives(jax.make_jaxpr(_kind(kind)[0])(params, batch).jaxpr)
    assert _kernels(before) == kernels
    assert before["dot_general"] - found["dot_general"] == products * sum(kernels.values()) // 2

    _without_policy(monkeypatch)
    want_loss, want = jax.jit(_kind(kind)[0])(params, batch)
    _same_numbers(loss, grads, want_loss, want)


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_model_that_recomputes_nothing_is_untouched_by_the_policy(kind, monkeypatch):
    """Without ``remat`` no policy is consulted and the names (the flash
    call's two, the attention sub-layer's two) are identities: the same
    program whatever the policy says, one kernel call each way a layer."""
    grad, params, batch, _, kernels = _kind(kind, remat=False)
    jaxpr = jax.make_jaxpr(grad)(params, batch)
    found = _primitives(jaxpr.jaxpr)
    assert "checkpoint" not in found
    assert _kernels(found) == kernels
    for policy in (None, _FLASH_ALONE):
        _with_policy(monkeypatch, policy)
        assert str(jax.make_jaxpr(_kind(kind, remat=False)[0])(params, batch)) == str(jaxpr)


def test_the_backward_is_handed_one_float32_a_position():
    """The kernel writes its row statistic ``_STAT_W`` lanes wide, which pad to
    128 in HBM; what is kept for the backward is ``[batch·heads, L]``."""
    q = jnp.ones((4, 128, 64), jnp.float32)
    _, (_, _, _, _, o, lse) = fa._flash_attention_fwd(q, q, q, None, 2, 0.125, True, 128, 128, True)
    assert o.shape == (4, 128, 64) and lse.shape == (4, 128) and lse.dtype == jnp.float32


def test_the_output_is_named_with_its_heads_merged():
    """``o`` is kept ``[batch, L, heads·d_v]``, as the models' output
    projections read it: the kernel's ``[batch·heads, L, 64]`` pads its 64
    lanes to 128 on the chip."""
    o = jnp.arange(6 * 8 * 4, dtype=jnp.float32).reshape(6, 8, 4)  # 2 rows of 3 heads
    merged = fa._heads_last(o, 3)
    want = o.reshape(2, 3, 8, 4).transpose(0, 2, 1, 3).reshape(2, 8, 12)
    np.testing.assert_array_equal(merged, want)
    np.testing.assert_array_equal(fa._heads_first(merged, 3), o)
    q = jnp.ones((6, 128, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q: fa._flash_attention_fwd(q, q, q, None, 3, 0.125, True, 128, 128, True))(q)
    named = {eqn.params["name"]: eqn.outvars[0].aval.shape for eqn in jaxpr.eqns if eqn.primitive.name == "name"}
    assert named == {fa.KEPT_O: (2, 128, 192), fa.KEPT_LSE: (6, 128)}
