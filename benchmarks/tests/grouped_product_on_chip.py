"""Read, on the chip, the two grouped matrix products the routed experts could
run on, at the cell's own shapes: ``jax.lax.ragged_dot`` (what
``ops/grouped_matmul.py`` calls) and the Pallas ``megablox.gmm``. Not part of
a benchmark run and not run by pytest:

    python3 benchmarks/tests/grouped_product_on_chip.py          # a TPU
    python3 benchmarks/tests/grouped_product_on_chip.py --cpu    # rehearsal, tiny sizes

A sorted buffer of ``tokens * 4`` slot rows of which the held experts' groups
cover ``--held-share`` (0.125 under even routing, 1.0 the bound): a layer's
three products forward, and forward + backward, per call; whether the two
agree; and what each leaves in the rows past its groups. One JSON line each.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", action="store_true")
    parser.add_argument("--tokens", type=int, default=8192)
    parser.add_argument("--held-share", default="0.125,0.25,1.0")
    args = parser.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from tensorflowonspark_tpu.ops.grouped_matmul import grouped_matmul

    d, f, held, k = (64, 32, 4, 4) if args.cpu else (3584, 1024, 8, 4)
    tokens = 64 if args.cpu else args.tokens
    slots = tokens * k
    dt = jnp.float32 if args.cpu else jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (slots, d), dt)
    gate, up = (jax.random.normal(key, (held, d, f), dt) * d ** -0.5 for key in keys[1:3])
    down = jax.random.normal(keys[3], (held, f, d), dt) * f ** -0.5

    def layer(product):
        def fn(x, gate, up, down, sizes):
            hidden = jax.nn.silu(product(x, gate, sizes)) * product(x, up, sizes)
            return product(hidden, down, sizes)
        return fn

    def gmm(lhs, rhs, sizes):
        used = (jnp.arange(lhs.shape[0]) < jnp.sum(sizes))[:, None]
        out = megablox.gmm(jnp.where(used, lhs, 0), rhs, sizes, lhs.dtype, (512, 1024, 1024) if not args.cpu
                           else (8, 32, 32), interpret=args.cpu)
        return jnp.where(used, out, 0)

    def timed(fn, *operands, repeat=5):
        jax.block_until_ready(fn(*operands))
        t0 = time.perf_counter()
        for _ in range(repeat):
            out = fn(*operands)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / repeat * 1e3

    for share in (float(s) for s in args.held_share.split(",")):
        rows = int(slots * share)
        rng = np.random.default_rng(1)
        cuts = np.sort(rng.integers(0, rows + 1, held - 1))
        sizes = jnp.asarray(np.diff(np.concatenate([[0], cuts, [rows]])), jnp.int32)
        line = {"held_share": share, "slots": slots, "rows_in_groups": rows, "group_sizes": sizes.tolist()}
        outs = {}
        for name, product in (("ragged_dot", grouped_matmul), ("megablox_gmm", gmm)):
            fwd = jax.jit(layer(product))
            both = jax.jit(jax.grad(lambda *a: jnp.sum(layer(product)(*a).astype(jnp.float32) ** 2), argnums=(0, 1, 2, 3)))
            try:
                line[name + "_fwd_ms"] = timed(fwd, x, gate, up, down, sizes)
                line[name + "_fwd_bwd_ms"] = timed(both, x, gate, up, down, sizes)
                outs[name] = fwd(x, gate, up, down, sizes).astype(jnp.float32)
                raw = jax.jit(lambda a, b, s: jax.lax.ragged_dot(a, b, s))(x, gate, sizes) if name == "ragged_dot" else \
                    megablox.gmm(x, gate, sizes, x.dtype, (512, 1024, 1024) if not args.cpu else (8, 32, 32),
                                 interpret=args.cpu)
                tail = raw[rows:].astype(jnp.float32)
                line[name + "_tail_abs_max"] = float(jnp.abs(tail).max()) if tail.size else 0.0
                line[name + "_tail_finite"] = bool(jnp.isfinite(tail).all()) if tail.size else True
            except Exception as e:  # one may refuse a shape the other takes
                line[name + "_error"] = repr(e)[:300]
        if len(outs) == 2:
            a, b = outs["ragged_dot"], outs["megablox_gmm"]
            line["max_abs_diff"] = float(jnp.abs(a - b).max())
            line["out_abs_max"] = float(jnp.abs(a).max())
        flops = 2 * 3 * d * f * rows
        line["fwd_gflop"] = flops / 1e9
        print(json.dumps(line), flush=True)
    print(json.dumps({"device": jax.devices()[0].device_kind, "platform": jax.devices()[0].platform}))


if __name__ == "__main__":
    main()
