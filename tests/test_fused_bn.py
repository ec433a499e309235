"""Fused pallas BatchNorm numerics vs flax.linen.BatchNorm (interpret mode).

The kernels are the BN-slice experiment (PERF.md Findings): whatever the
on-chip timing says, the math must be exactly training-mode batch norm —
forward, batch statistics, and the full custom VJP (dx folds the statistics'
dependency on x; dgamma/dbeta are the usual reductions)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from tensorflowonspark_tpu.ops.fused_bn import FusedBatchNorm, fused_batch_norm


@pytest.mark.parametrize("n_ch", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_forward_matches_reference_math(n_ch, dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 4, 4, n_ch)) * 2 + 1, dtype)
    gamma = jnp.asarray(rng.standard_normal(n_ch), jnp.float32)
    beta = jnp.asarray(rng.standard_normal(n_ch), jnp.float32)

    # block_r=16 forces multi-step grid accumulation (rows=64)
    y, mean, var = fused_batch_norm(x, gamma, beta, block_r=16, interpret=True)
    assert y.dtype == dtype

    xf = np.asarray(x, np.float64).reshape(-1, n_ch)
    ref_mean = xf.mean(axis=0)
    ref_var = xf.var(axis=0)
    ref_y = (xf - ref_mean) / np.sqrt(ref_var + 1e-5) * np.asarray(gamma) + np.asarray(beta)
    tol = 5e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(mean), ref_mean, atol=tol)
    np.testing.assert_allclose(np.asarray(var), ref_var, atol=tol)
    np.testing.assert_allclose(
        np.asarray(y, np.float64).reshape(-1, n_ch), ref_y, atol=tol * 100
    )


def test_gradients_match_flax_batchnorm():
    """d(loss)/d(x, gamma, beta) must equal flax's training-mode BN grads —
    including the batch-statistics terms in dx."""
    rng = np.random.default_rng(1)
    n_ch = 64
    x = jnp.asarray(rng.standard_normal((2, 4, 4, n_ch)), jnp.float32)
    gamma = jnp.asarray(rng.standard_normal(n_ch), jnp.float32)
    beta = jnp.asarray(rng.standard_normal(n_ch), jnp.float32)
    w = jnp.asarray(rng.standard_normal((2, 4, 4, n_ch)), jnp.float32)  # loss weights

    def fused_loss(x, gamma, beta):
        y, _, _ = fused_batch_norm(x, gamma, beta, block_r=16, interpret=True)
        return jnp.sum(y * w)

    bn = nn.BatchNorm(use_running_average=False, momentum=0.9, epsilon=1e-5)
    variables = bn.init(jax.random.PRNGKey(0), x)

    def flax_loss(x, gamma, beta):
        params = {"params": {"scale": gamma, "bias": beta},
                  "batch_stats": variables["batch_stats"]}
        y, _ = bn.apply(params, x, mutable=["batch_stats"])
        return jnp.sum(y * w)

    got = jax.grad(fused_loss, argnums=(0, 1, 2))(x, gamma, beta)
    want = jax.grad(flax_loss, argnums=(0, 1, 2))(x, gamma, beta)
    for g, r, name in zip(got, want, ("dx", "dgamma", "dbeta")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-4, err_msg=name)


def test_module_matches_flax_module_and_updates_running_stats():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 64)) + 0.5, jnp.float32)

    fused = FusedBatchNorm(momentum=0.9, interpret=True, block_r=32)
    ref = nn.BatchNorm(momentum=0.9, epsilon=1e-5)
    fvars = fused.init(jax.random.PRNGKey(0), x, use_running_average=False)
    rvars = ref.init(jax.random.PRNGKey(0), x, use_running_average=False)
    # identical variable structure: checkpoints interchange
    assert jax.tree.structure(fvars) == jax.tree.structure(rvars)

    fy, fmut = fused.apply(fvars, x, use_running_average=False, mutable=["batch_stats"])
    ry, rmut = ref.apply(rvars, x, use_running_average=False, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(fy), np.asarray(ry), atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(fmut["batch_stats"]["mean"]),
        np.asarray(rmut["batch_stats"]["mean"]), atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(fmut["batch_stats"]["var"]),
        np.asarray(rmut["batch_stats"]["var"]), atol=1e-4,
    )

    # eval mode uses the (updated) running stats, same as flax
    fe = fused.apply(
        {"params": fvars["params"], "batch_stats": fmut["batch_stats"]},
        x, use_running_average=True,
    )
    re = ref.apply(
        {"params": rvars["params"], "batch_stats": rmut["batch_stats"]},
        x, use_running_average=True,
    )
    np.testing.assert_allclose(np.asarray(fe), np.asarray(re), atol=1e-4)


def test_odd_rows_fall_back_instead_of_raising(caplog):
    """An odd per-shard batch (rows=7*5*5=175: no 8..block_r power-of-two
    divisor) must not crash the module at trace time: the train path logs a
    warning and falls back to the plain XLA spelling, matching flax BN in
    forward, running stats, and gradients. Direct ``fused_batch_norm``
    callers still get the loud error."""
    import logging

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((7, 5, 5, 32)) * 1.5 + 0.25, jnp.float32)
    w = jnp.asarray(rng.standard_normal(x.shape), jnp.float32)

    fused = FusedBatchNorm(momentum=0.9, interpret=True, block_r=16)
    ref = nn.BatchNorm(momentum=0.9, epsilon=1e-5)
    fvars = fused.init(jax.random.PRNGKey(0), x, use_running_average=False)
    rvars = ref.init(jax.random.PRNGKey(0), x, use_running_average=False)

    with caplog.at_level(logging.WARNING, logger="tensorflowonspark_tpu.ops.fused_bn"):
        fy, fmut = fused.apply(fvars, x, use_running_average=False, mutable=["batch_stats"])
    assert any("falling back" in r.getMessage() for r in caplog.records)

    ry, rmut = ref.apply(rvars, x, use_running_average=False, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(fy), np.asarray(ry), atol=1e-4)
    for stat, tol in (("mean", 1e-5), ("var", 1e-4)):
        np.testing.assert_allclose(
            np.asarray(fmut["batch_stats"][stat]),
            np.asarray(rmut["batch_stats"][stat]), atol=tol,
        )

    # gradients flow like flax's (batch-statistics terms included)
    def make_loss(model, variables):
        def f(params):
            y, _ = model.apply(
                {"params": params, "batch_stats": variables["batch_stats"]},
                x, use_running_average=False, mutable=["batch_stats"],
            )
            return jnp.sum(y * w)

        return f

    got = jax.grad(make_loss(fused, fvars))(fvars["params"])
    want = jax.grad(make_loss(ref, rvars))(rvars["params"])
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3),
        got, want,
    )

    gamma = jnp.ones(32, jnp.float32)
    beta = jnp.zeros(32, jnp.float32)
    with pytest.raises(ValueError, match="block divisor"):
        fused_batch_norm(x, gamma, beta, block_r=16, interpret=True)


def test_resnet_bn_impl_pallas_trains():
    """resnet56(bn_impl='pallas') runs a forward+backward on CPU (interpret
    mode via the model's backend check) and matches the flax-BN model's loss
    at identical params."""
    from tensorflowonspark_tpu.models import resnet

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((4, 8, 8, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, 4))

    flax_model = resnet.ResNet(
        stage_sizes=(1,), filters=(16,), num_classes=10, bottleneck=False,
        stem="cifar", bn_impl="flax",
    )
    pallas_model = resnet.ResNet(
        stage_sizes=(1,), filters=(16,), num_classes=10, bottleneck=False,
        stem="cifar", bn_impl="pallas",
    )
    variables = flax_model.init(jax.random.PRNGKey(0), x, train=False)

    def loss(model, variables):
        def f(params):
            logits, _ = model.apply(
                {"params": params, "batch_stats": variables["batch_stats"]},
                x, train=True, mutable=["batch_stats"],
            )
            return optax_ce(logits, labels)

        return jax.value_and_grad(f)(variables["params"])

    import optax

    def optax_ce(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()

    flax_loss, flax_grads = loss(flax_model, variables)
    pallas_loss, pallas_grads = loss(pallas_model, variables)
    np.testing.assert_allclose(float(pallas_loss), float(flax_loss), atol=1e-4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3),
        flax_grads, pallas_grads,
    )
