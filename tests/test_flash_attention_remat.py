"""What a recomputed layer keeps of its flash call (``flash_attention.REMAT_POLICY``):
the output and the log-sum-exp, so the gradient of a model of ``L`` recomputed
layers runs the forward kernel ``L`` times and not ``2 L``, and computes what it
computed before, bit for bit. The kernels run in the Pallas interpreter."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_testutil import REF, packed_batch, program_config
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


def _flash_calls(grad, params, batch):
    found = _primitives(jax.make_jaxpr(grad)(params, batch).jaxpr)
    return {name: count for name, count in found.items() if name.startswith("flash_")}


def _without_policy(monkeypatch):
    for module in (transformer, decoder):
        monkeypatch.setattr(module, "REMAT_POLICY", None)


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
    assert np.asarray(loss) == np.asarray(want_loss) and np.isfinite(loss)
    flat, flat_want = jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree.leaves(want)
    assert len(flat) == len(flat_want)
    for (path, got), leaf in zip(flat, flat_want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(leaf), err_msg=jax.tree_util.keystr(path))
    assert any(np.asarray(leaf).any() for leaf in flat_want)


@pytest.mark.parametrize("family", ["transformer", "decoder"])
def test_a_model_that_recomputes_nothing_is_untouched_by_the_policy(family, monkeypatch):
    """Without ``remat`` no policy is consulted and the names are identities:
    the same program whatever the policy says, one kernel call each way a layer."""
    grad, params, batch = _case(family, True, remat=False)
    jaxpr = jax.make_jaxpr(grad)(params, batch)
    found = _primitives(jaxpr.jaxpr)
    assert "checkpoint" not in found
    assert (found["flash_fwd_seg"], found["flash_bwd_dkv_seg"]) == (LAYERS, LAYERS)
    _without_policy(monkeypatch)
    grad, _, _ = _case(family, True, remat=False)
    assert str(jax.make_jaxpr(grad)(params, batch)) == str(jaxpr)


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
