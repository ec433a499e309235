"""The ``ssm_lm`` family: packed-document training of the plan-built decoder
(``tensorflowonspark_tpu/models/decoder.py``) in its decoder-hybrid-decoder
dialect — Mamba layers and windowed differential attention into one full
layer whose keys, values and scan memory gated memory units and cross
attention read; LayerNorm, no positional encoding, a tied head. Built from the
program's public entry points in the order
``examples/transformer/transformer_spark.py --model decoder --model_config``
calls them; the benchmark's child (``child.py``) owns the loop, the window and
the spans; this file builds what it drives.

As in ``families/swa_lm.py`` the corpus and the packing are seeded by the
traffic file's ``corpus.seed`` (which documents share a row decides how many
attention blocks a step computes and where the scans restart) and ``--seed``
draws every weight (``reference/ssm_lm.init_params``). The text plane is told
the model's window and that it scans (``TextPipeline(attention_window=,
scan_restarts=True)``) and counts the windowed layers' blocks and the scans'
restarts beside the full layers' blocks. Every parameter is trained: there is
no router to calibrate or freeze.

One row a step, and the rows differ (a lone document of 8192 has twice the
visible pairs of two of 4000), so what the last ``trace_steps`` batches held
is kept apart from the window's sums (``parts["traced_*"]``), as
``families/swa_lm.py`` does: the flash kernels' rooflines set the traced
steps' device time against the traced rows' pairs.
"""

import collections
import itertools
import os
import time

import numpy as np

from benchmarks import corpus, flops_ssm_lm
from benchmarks.families import common
from benchmarks.reference import ssm_lm as reference

#: keys of the cell's configuration file that are the benchmark's own; every
#: other key is the model's and goes to the program, which refuses what it
#: does not know
BENCHMARK_KEYS = (
    "family", "source", "why", "param_dtype", "optimizer", "deployment", "parameters", "source_config", "reduced",
    "reduced_why", "assumed",
)


def model_config(cfg, remat):
    """What ``--model_config`` would hold: the configuration's model keys."""
    return dict({k: v for k, v in cfg.items() if k not in BENCHMARK_KEYS}, remat=remat)


def build(spec, ctx, parts):
    import jax

    from tensorflowonspark_tpu import models, parallel
    from tensorflowonspark_tpu import tfrecord as tfr
    from tensorflowonspark_tpu.data import TextPipeline, Tokenizer, shard_files
    from tensorflowonspark_tpu.models import transformer
    from tensorflowonspark_tpu.train import SyncDataParallel

    cfg, traffic, seed = spec["config"], spec["traffic"], spec["seed"]
    ctx.initialize_distributed()
    axes = dict(traffic["mesh"])
    mesh = parallel.local_mesh(axes) if ctx.num_processes == 1 else ctx.mesh(axes)
    chips = int(mesh.devices.size)
    rows, seq = traffic["batch_per_chip"] * chips, traffic["seq_len"]

    # first, so that a program without the model fails before any work
    model = models.get_model("decoder", mesh=mesh, **model_config(cfg, traffic["remat"]))
    t0 = time.perf_counter()
    data_dir = os.path.join(spec["scratch"], "corpus")
    made = corpus.make_text(
        data_dir, traffic["corpus"], traffic["corpus"]["tokens_per_chip"] * chips, traffic["corpus"]["seed"])
    parts["corpus_s"] = time.perf_counter() - t0

    files = shard_files(tfr.list_shards(data_dir), ctx.num_workers, ctx.executor_id)
    tokenizer = Tokenizer(kind=traffic["tokenizer"], vocab_size=cfg["vocab_size"])
    window = model.cfg.sliding_window
    pipe = TextPipeline(
        files, tokenizer, seq_len=seq + 1, batch_size=rows, seed=traffic["corpus"]["seed"],
        epochs=None, pack_workers=traffic["pack_workers"], pack_ahead=traffic["pack_ahead"],
        attention_window=window, scan_restarts=True,
    )
    stream = iter(pipe)
    key = common.seed_key(seed)

    strategy = SyncDataParallel(mesh)
    optimizer, first_gradient = common.make_optimizer(cfg["optimizer"])
    t0 = time.perf_counter()
    state = common.seeded_state(strategy, optimizer, lambda k: {"params": reference.init_params(k, cfg)}, key)
    jax.block_until_ready(state.params)
    parts["state_s"] = time.perf_counter() - t0
    step = strategy.compile_train_step(transformer.make_loss_fn(model), optimizer, has_aux=True)

    counts = {"rows": 0, "real_tokens": 0, "pairs": 0, "pairs_window": 0}
    kept = []
    last = collections.deque(maxlen=traffic["trace_steps"])

    def packed_batches():
        for batch in stream:
            seg = batch["segment_ids"][:, :-1]
            pairs = flops_ssm_lm.visible_pairs(seg), flops_ssm_lm.visible_pairs(seg, window)
            counts["rows"] += seg.shape[0]
            counts["real_tokens"] += int((seg > 0).sum())
            counts["pairs"] += pairs[0]
            counts["pairs_window"] += pairs[1]
            last.append((seg.shape[0],) + pairs)
            parts["traced_rows"], parts["traced_pairs"], parts["traced_pairs_window"] = map(sum, zip(*last))
            if len(kept) < traffic["check_steps"]:
                kept.append({k: np.array(v) for k, v in batch.items()})
            yield strategy.shard_batch(batch)

    first_grad, param_change = common.norm_readers(first_gradient, lambda k: reference.init_params(k, cfg), key)

    def flops_per_step(window_counts):
        per_row = rows / max(window_counts["rows"], 1)
        return flops_ssm_lm.matmul_flops(cfg, rows * seq) + flops_ssm_lm.attention_flops(
            cfg, window_counts["pairs"] * per_row, window_counts["pairs_window"] * per_row)

    def close():
        stream.close()
        step.drain()

    return common.job(
        state=state, step=step, batches=packed_batches(), close=close,
        mesh=mesh, chips=chips, unit="tokens", units_per_step=rows * seq,
        counts=counts, kept=kept, corpus=made,
        first_grad=first_grad, param_change=param_change,
        reference=lambda batches, quant=None: reference.follow(
            cfg, key, batches, list(mesh.devices.flat), quant=quant),
        flops_per_step=flops_per_step,
    )
