"""Read, on the chip, what one sub-layer's residual path takes alone at the
``xing4-a4b.packed8k`` cell's shape (streams ``1 x 8192 x 4 x 3584``,
bfloat16): the evidence ``ops/hyper_connection.py`` stands on. Not part of a
benchmark run and not run by pytest:

    python3 benchmarks/tests/mhc_on_chip.py                    # a TPU
    python3 benchmarks/tests/mhc_on_chip.py --parent .parent   # and another checkout's path
    python3 benchmarks/tests/mhc_on_chip.py --cpu              # rehearsal, tiny sizes, interpreted

The path is ``models/decoder.HyperConnection`` as a layer calls it — the
first pass and the maps, ``F``, ``merge`` — with ``F`` the identity, so that
nothing but the residual path is timed; weights seeded as the benchmark seeds
a sub-layer's (``reference/moe_lm.leaf_shapes``). Each checkout is given the
streams as its own ``Decoder`` carries them: ``[rows, seq, n * d]`` here,
``[rows, seq, n, d]`` in ``--parent`` (a checkout from before PR 31). One JSON line a checkout:
forward alone and forward + backward per call (the backward by ``jax.vjp``
from a given cotangent, so no loss is computed), GB/s against
``mhc_bytes.sublayer_bytes`` (what any implementation must move), a step's
worth for the cell (ten sub-layers, forward twice where the traffic
recomputes), and the largest difference of the result and of every gradient
from the plain float32 reference under ``highest``
(``reference/moe_lm.hyper_connected``), each over the reference's largest
entry. For this checkout also each of the four kernels alone.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", action="store_true", help="rehearse on the CPU: tiny sizes, interpreted kernels")
    parser.add_argument("--parent", help="another checkout whose residual path is timed and compared")
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--seed", type=int, default=3100000007)
    args = parser.parse_args()

    from tensorflowonspark_tpu import util

    if args.cpu:
        util.force_platform("cpu")
    util.place_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import mhc_bytes
    from benchmarks.families import moe_lm
    from benchmarks.reference import moe_lm as reference
    from tensorflowonspark_tpu.models import decoder as mine

    with open(os.path.join(ROOT, "benchmarks", "traffic", "packed8k.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "configs", "xing4-a4b.json")) as f:
        config = json.load(f)
    rows, seq = traffic["batch_per_chip"], traffic["seq_len"]
    if args.cpu:
        config, rows, seq, args.iters = dict(config, hidden_size=128, dtype="float32"), 2, 64, 2
    n, d, dtype = config["hc_mult"], config["hidden_size"], jnp.dtype(config["dtype"])
    device = jax.devices()[0]
    print(json.dumps({"device": {"platform": device.platform, "kind": device.device_kind}, "streams": [rows, seq, n, d],
                      "dtype": dtype.name, "iters": args.iters}), flush=True)

    key = jax.random.PRNGKey(args.seed % (2 ** 31))
    std = (n * d) ** -0.5
    shapes = {"phi_pre": ((n, d, n), std), "phi_post": ((n, d, n), std), "phi_res": ((n, d, n * n), std),
              "b_pre": ((n,), 0.5), "b_post": ((n,), 0.5)}
    params = {name: scale * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
              for i, (name, (shape, scale)) in enumerate(shapes.items())}
    params.update({"alpha_" + name: jnp.float32(0.01) for name in ("pre", "post", "res")}, b_res=2.0 * jnp.eye(n))
    streams = jax.random.normal(jax.random.fold_in(key, 10), (rows, seq, n * d), jnp.float32).astype(dtype)
    cotangent = jax.random.normal(jax.random.fold_in(key, 11), (rows, seq, n * d), jnp.float32).astype(dtype)

    def passes(module):
        cfg = module.DecoderConfig.from_dict(moe_lm.model_config(config, traffic["remat"]))

        def forward(p, x):
            h, maps = module.HyperConnection(cfg).apply({"params": p}, x)
            return module.HyperConnection.merge(x, maps, h)

        def both(p, x, g):
            out, vjp = jax.vjp(forward, p, x)
            return (out,) + vjp(g)

        return jax.jit(forward), jax.jit(both)

    def seconds(fn, *operands):
        jax.block_until_ready(fn(*operands))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*operands)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters

    def plain(p, x, g):
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(lambda p, x: reference.hyper_connected(x, p, lambda h: h, config), p, x)
            return (out,) + vjp(g)

    wide = lambda x: x.astype(jnp.float32).reshape(rows, seq, n, d)  # noqa: E731
    want = jax.jit(plain)(params, wide(streams), wide(cotangent))
    fwd_bytes, bwd_bytes = mhc_bytes.sublayer_bytes(config, rows, seq, remat=False)
    sublayers = 2 * config["num_hidden_layers"]

    def reading(label, module, carried):
        forward, both = passes(module)
        x, g = streams.reshape(carried), cotangent.reshape(carried)  # outside the timed program: a copy on a chip
        fwd, fwd_bwd = seconds(forward, params, x), seconds(both, params, x, g)
        bwd = fwd_bwd - fwd
        got = both(params, x, g)
        gaps = {}
        for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
            name = jax.tree_util.keystr(path).replace("[0]", "out").replace("[1]", "").replace("[2]", "d_streams")
            a, b = np.asarray(a, np.float32).reshape(b.shape), np.asarray(b, np.float32)
            gaps[name.strip("[']")] = float(np.abs(a - b).max() / np.abs(b).max())
        print(json.dumps({
            "path": label, "forward_ms": 1e3 * fwd, "backward_ms": 1e3 * bwd,
            "forward_gb_per_s": fwd_bytes / fwd / 1e9, "backward_gb_per_s": bwd_bytes / bwd / 1e9,
            "step_worth_ms": 1e3 * sublayers * ((2 if traffic["remat"] else 1) * fwd + bwd),
            "largest_gap": max(gaps.values()), "gaps": gaps}), flush=True)

    if args.parent:
        path = os.path.join(args.parent, "tensorflowonspark_tpu", "models", "decoder.py")
        spec = importlib.util.spec_from_file_location("parent_decoder", path)
        theirs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(theirs)
        reading("parent", theirs, (rows, seq, n, d))
    reading("this checkout", mine, (rows, seq, n * d))

    from tensorflowonspark_tpu.ops import hyper_connection as hc

    interpret, f32 = args.cpu, jnp.float32
    k, tokens = 2 * n + n * n, rows * seq
    x, g = streams.reshape(tokens, n * d), cotangent.reshape(tokens, n * d)
    y = x[:, :d]
    phit = jnp.concatenate([params["phi_pre"], params["phi_post"], params["phi_res"]], -1).reshape(n * d, k).T.astype(dtype)
    ab = jnp.stack([jnp.full((n,), 0.01, f32), params["b_pre"]])
    small = lambda i, width: jax.random.uniform(jax.random.fold_in(key, i), (tokens, width), f32)  # noqa: E731
    stream, one = mhc_bytes.stream_bytes(config, rows, seq)
    for name, fn, operands, moved in (
            ("mhc_read", hc._read_call, (x, phit, ab), stream + one),
            ("mhc_merge", hc._merge_call, (x, y, small(20, n * n + n)), 2 * stream + one),
            ("mhc_merge_bwd", hc._merge_bwd_call, (g, x, y, small(21, n * n + n)), 3 * stream + 2 * one),
            ("mhc_read_bwd", hc._read_bwd_call,
             (x, g, y, small(22, k), small(23, k), 1.0 + small(24, 1), phit, ab), 3 * stream + one)):
        took = seconds(jax.jit(lambda *a, fn=fn: fn(*a, interpret)), *operands)
        print(json.dumps({"kernel": name, "ms": 1e3 * took, "gb_per_s": moved / took / 1e9}), flush=True)


if __name__ == "__main__":
    main()
