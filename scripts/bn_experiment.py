"""BN-slice experiment (VERDICT r4 item 3): flax BN vs fused pallas BN.

The r4 breakdown measured the full ResNet-50 train step at
106.4 ms/iter with BatchNorm costing 28% of it (77.4 ms/iter with BN deleted).
This script times the SAME guarded harness with ``bn_impl="flax"`` vs
``bn_impl="pallas"`` (ops/fused_bn.py) interleaved, and prints one JSON line
per variant. Guards carried over from r4 (each one was a measured trap):

* K=16 steps fused in one ``lax.scan`` dispatch — a per-dispatch
  dispatch+fence cost (~100 ms when this was written) amortizes to <1%;
* the input batch is CARRY-CHAINED through the loss (x += loss * 1e-6), so
  XLA can neither hoist batch-invariant work out of the scan nor dead-code
  steps (naive scan microbenches here read 400+ TFLOP/s);
* the fence is a ONE-element device_get of the last step's loss (which
  depends on every prior step), never block_until_ready;
* variants interleave inside one process and compare per-round medians.

Run on the TPU:  python scripts/bn_experiment.py
Env: BN_BS (256), BN_K (16), BN_ROUNDS (3), BN_IMG (224), BN_VARIANTS.
"""

import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tensorflowonspark_tpu.models import resnet  # noqa: E402

BS = int(os.environ.get("BN_BS", "256"))
K = int(os.environ.get("BN_K", "16"))
ROUNDS = int(os.environ.get("BN_ROUNDS", "3"))
IMG = int(os.environ.get("BN_IMG", "224"))
VARIANTS = os.environ.get("BN_VARIANTS", "flax,pallas").split(",")

# ResNet-50 training step ~= 3 * 4.1 GFLOPs/img forward
FLOPS_PER_IMG = 3 * 4.1e9 * (IMG / 224) ** 2


def build(bn_impl):
    model = resnet.resnet50(num_classes=1000, dtype=jnp.bfloat16, bn_impl=bn_impl)
    rng = jax.random.PRNGKey(0)
    variables = model.init(rng, jnp.zeros((1, IMG, IMG, 3), jnp.bfloat16), train=False)
    params, bstats = variables["params"], variables["batch_stats"]
    opt = optax.sgd(0.1, momentum=0.9)
    opt_state = opt.init(params)

    def loss_fn(params, bstats, x, y):
        logits, mut = model.apply(
            {"params": params, "batch_stats": bstats}, x, train=True,
            mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        return loss, mut["batch_stats"]

    @jax.jit
    def k_steps(params, bstats, opt_state, x, y):
        def body(carry, _):
            params, bstats, opt_state, x = carry
            (loss, bstats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, bstats, x, y
            )
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            # carry-chain: the next step's batch depends on this step's loss
            x = x + (loss * 1e-6).astype(x.dtype)
            return (params, bstats, opt_state, x), loss

        (params, bstats, opt_state, x), losses = jax.lax.scan(
            body, (params, bstats, opt_state, x), None, length=K
        )
        return params, bstats, opt_state, losses[-1]

    return params, bstats, opt_state, k_steps


def main():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((BS, IMG, IMG, 3)), jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, 1000, BS))

    states = {}
    for name in VARIANTS:
        params, bstats, opt_state, k_steps = build(name)
        # warmup = compile + one steady dispatch
        params, bstats, opt_state, loss = k_steps(params, bstats, opt_state, x, y)
        float(np.asarray(jax.device_get(loss)))
        states[name] = [params, bstats, opt_state, k_steps, []]
        print("compiled variant {!r}".format(name), file=sys.stderr)

    for _ in range(ROUNDS):  # interleaved A/B
        for name in VARIANTS:
            st = states[name]
            t0 = time.perf_counter()
            st[0], st[1], st[2], loss = st[3](st[0], st[1], st[2], x, y)
            float(np.asarray(jax.device_get(loss)))  # 1-element fence
            st[4].append((time.perf_counter() - t0) / K * 1e3)

    for name in VARIANTS:
        ms = statistics.median(states[name][4])
        print(json.dumps({
            "variant": "bn_" + name,
            "ms_per_iter": round(ms, 2),
            "img_per_sec": round(BS / ms * 1e3, 1),
            "tflops": round(FLOPS_PER_IMG * BS / ms / 1e9, 1),
            "rounds_ms": [round(v, 2) for v in states[name][4]],
            "bs": BS, "k": K, "img": IMG,
        }))


if __name__ == "__main__":
    main()
