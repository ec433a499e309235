"""Read, on the chip, what the segmented flash kernels take at each block size
on rows packed by the ``packed4k`` law: the evidence the constants
``ops/flash_blocks.SEGMENTED_BLOCK_Q`` / ``_K`` stand on. Not part of a
benchmark run and not run by pytest:

    python3 benchmarks/tests/flash_blocks_on_chip.py                    # a TPU
    python3 benchmarks/tests/flash_blocks_on_chip.py --parent .parent   # and another checkout's kernels
    python3 benchmarks/tests/flash_blocks_on_chip.py --cpu              # rehearsal, tiny sizes, interpreted

Batches of ``--rows`` rows are packed from the traffic file's document law by
the program's own ``pack_bins`` (the text plane's window of two batches), ids
as the text plane writes them. For every ``--blocks`` pair: the forward alone
and forward + backward (dq and dk/dv) per call, a step's worth
(``2 x forward + backward``: the cell recomputes every block), and the share
of the causal triangle's blocks that the map needs. With ``--parent``: that
checkout's kernels timed the same way at its own defaults, and o, dq, dk and
dv of the two compared bit for bit at the parent's block sizes. Last, the
unsegmented causal kernels (no cell runs them). One JSON line each.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def packed_batches(law, rows, seq, batches, seed):
    """``int32 [batches, rows, seq]`` segment ids of rows of ``seq + 1``
    packed two batches ahead, cut to the columns the LM attends."""
    import numpy as np

    from benchmarks import corpus
    from tensorflowonspark_tpu.data import pack_bins

    count = int(1.25 * (batches + 2) * rows * (seq + 1) / corpus.mean_doc_length(law))
    lengths = list(np.random.default_rng(seed).permutation(corpus.doc_lengths(law, count)))
    out, window = [], []
    while len(out) < batches:
        while sum(window) < 2 * rows * (seq + 1):
            window.append(int(lengths.pop()))
        bins = pack_bins(window, seq + 1)
        ids = np.zeros((rows, seq + 1), np.int32)
        for row, members in zip(ids, bins[:rows]):
            at = 0
            for doc, i in enumerate(members, start=1):
                row[at:at + window[i]] = doc
                at += window[i]
        out.append(ids[:, :-1])
        window = [window[i] for i in sorted(i for members in bins[rows:] for i in members)]
    return np.stack(out)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", action="store_true", help="rehearse on the CPU: tiny sizes, interpreted kernels")
    parser.add_argument("--parent", help="another checkout whose kernels are timed and compared")
    parser.add_argument("--blocks", default="512x512,512x256,256x256")
    parser.add_argument("--batches", type=int, default=6)
    parser.add_argument("--iters", type=int, default=24)
    parser.add_argument("--seed", type=int, default=2500000011)
    args = parser.parse_args()

    from tensorflowonspark_tpu import util

    if args.cpu:
        util.force_platform("cpu")
    util.place_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.ops import flash_attention as mine
    from tensorflowonspark_tpu.ops import flash_blocks

    with open(os.path.join(ROOT, "benchmarks", "traffic", "packed4k.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "configs", "lm1024.json")) as f:
        config = json.load(f)
    rows, seq = traffic["batch_per_chip"], traffic["seq_len"]
    heads, head_dim = config["n_heads"], config["head_dim"]
    blocks = [tuple(int(n) for n in pair.split("x")) for pair in args.blocks.split(",")]
    if args.cpu:
        seq, heads, blocks, args.iters, args.batches = 512, 2, [(64, 64), (64, 32)], 2, 2
    device = jax.devices()[0]
    print(json.dumps({"device": {"platform": device.platform, "kind": device.device_kind}, "rows": rows,
                      "seq": seq, "heads": heads, "head_dim": head_dim, "iters": args.iters}), flush=True)

    law = dict(traffic["corpus"]["doc_tokens"], max=min(traffic["corpus"]["doc_tokens"]["max"], seq))
    ids = packed_batches(law, rows, seq, args.batches, args.seed)
    rng = np.random.default_rng(args.seed)
    q, k, v, do = (jnp.asarray(rng.standard_normal((rows, heads, seq, head_dim)), jnp.bfloat16) for _ in range(4))
    segs = [jnp.asarray(batch) for batch in ids]

    def passes(module, segmented, **sizes):
        def forward(q, k, v, seg):
            return module.flash_attention(
                q, k, v, causal=True, segment_ids=seg if segmented else None, interpret=args.cpu, **sizes)

        def both(q, k, v, seg, do):
            o, vjp = jax.vjp(lambda q, k, v: forward(q, k, v, seg), q, k, v)
            return (o,) + vjp(do)

        return jax.jit(forward), jax.jit(both)

    def seconds(fn, *tail):
        jax.block_until_ready(fn(q, k, v, segs[0], *tail))
        t0 = time.perf_counter()
        for i in range(args.iters):
            out = fn(q, k, v, segs[i % len(segs)], *tail)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters

    def reading(label, module, segmented=True, **sizes):
        forward, both = passes(module, segmented, **sizes)
        fwd, fwd_bwd = seconds(forward), seconds(both, do)
        line = {"kernels": label, "forward_ms": 1e3 * fwd, "forward_backward_ms": 1e3 * fwd_bwd,
                "backward_ms": 1e3 * (fwd_bwd - fwd), "step_worth_ms": 1e3 * (fwd + fwd_bwd)}
        print(json.dumps(line), flush=True)
        return both

    theirs = None
    if args.parent:
        path = os.path.join(args.parent, "tensorflowonspark_tpu", "ops", "flash_attention.py")
        spec = importlib.util.spec_from_file_location("parent_flash_attention", path)
        theirs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(theirs)
        parent_both = reading("parent segmented", theirs)
    for block_q, block_k in blocks:
        needed = sum(int(flash_blocks.needed_blocks(batch, block_q, block_k).sum()) for batch in ids)
        dense = int(flash_blocks.causal_blocks(seq // block_q, seq // block_k, block_q, block_k).sum()) * ids[0].shape[0]
        print(json.dumps({"blocks": [block_q, block_k], "needed_pct": 100.0 * needed / (dense * len(ids))}))
        both = reading("segmented {}x{}".format(block_q, block_k), mine, block_q=block_q, block_k=block_k)
        if theirs is not None and (block_q, block_k) == (theirs.DEFAULT_BLOCK_Q, theirs.DEFAULT_BLOCK_K):
            differing = {}
            for seg in segs:
                for name, a, b in zip(("o", "dq", "dk", "dv"), both(q, k, v, seg, do), parent_both(q, k, v, seg, do)):
                    differing[name] = differing.get(name, 0) + int(
                        (np.asarray(a, np.float32) != np.asarray(b, np.float32)).sum())
            print(json.dumps({"against_parent_at": [block_q, block_k], "batches": len(segs),
                              "differing_elements": differing}), flush=True)
    if theirs is not None:
        reading("parent unsegmented", theirs, segmented=False)
    reading("unsegmented", mine, segmented=False)


if __name__ == "__main__":
    main()
