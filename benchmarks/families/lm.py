"""The ``lm`` family: packed-document language-model training, built from the
program's public entry points in the order
``examples/transformer/transformer_spark.py`` calls them. The benchmark's
child (``child.py``) owns the loop, the window and the spans; this file
builds what it drives."""

import os
import time

import numpy as np

from benchmarks import corpus, flops
from benchmarks.families import common
from benchmarks.reference import lm as reference


def build(spec, ctx, parts):
    import jax

    from tensorflowonspark_tpu import parallel
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

    t0 = time.perf_counter()
    data_dir = os.path.join(spec["scratch"], "corpus")
    made = corpus.make_text(data_dir, traffic["corpus"], traffic["corpus"]["tokens_per_chip"] * chips, seed)
    parts["corpus_s"] = time.perf_counter() - t0

    model = transformer.create_model(
        mesh=mesh, vocab_size=cfg["vocab_size"], d_model=cfg["d_model"], n_layers=cfg["n_layers"],
        n_heads=cfg["n_heads"], d_ff=cfg["d_ff"], max_seq_len=seq, dtype=cfg["dtype"],
        remat=traffic["remat"], attention=cfg["attention"],
    )
    strategy = SyncDataParallel(mesh)
    optimizer, first_gradient = common.make_optimizer(cfg["optimizer"])
    key = common.seed_key(seed)
    t0 = time.perf_counter()
    state = common.seeded_state(
        strategy, optimizer, lambda k: {"params": reference.init_params(k, cfg)}, key)
    jax.block_until_ready(state.params)
    parts["state_s"] = time.perf_counter() - t0
    step = strategy.compile_train_step(transformer.make_loss_fn(model), optimizer, has_aux=True)

    files = shard_files(tfr.list_shards(data_dir), ctx.num_workers, ctx.executor_id)
    tokenizer = Tokenizer(kind=traffic["tokenizer"], vocab_size=cfg["vocab_size"])
    pipe = TextPipeline(
        files, tokenizer, seq_len=seq + 1, batch_size=rows, seed=common.small_seed(seed),
        epochs=None, pack_workers=traffic["pack_workers"],
    )
    stream = iter(pipe)
    counts = {"rows": 0, "real_tokens": 0, "pairs": 0}
    kept = []

    def packed_batches():
        for batch in stream:
            seg = batch["segment_ids"][:, :-1]
            counts["rows"] += seg.shape[0]
            counts["real_tokens"] += int((seg > 0).sum())
            counts["pairs"] += flops.causal_pairs(seg)
            if len(kept) < traffic["check_steps"]:
                kept.append({k: np.array(v) for k, v in batch.items()})
            yield strategy.shard_batch(batch)

    first_grad, param_change = common.norm_readers(
        first_gradient, lambda k: reference.init_params(k, cfg), key)

    return common.job(
        state=state, step=step, batches=packed_batches(), close=stream.close,
        mesh=mesh, chips=chips, unit="tokens", units_per_step=rows * seq,
        counts=counts, kept=kept, corpus=made,
        first_grad=first_grad, param_change=param_change,
        reference=lambda batches, quant=None: reference.follow(
            cfg, key, batches, list(mesh.devices.flat), quant=quant),
        flops_per_step=lambda window: (
            flops.lm_matmul_flops_per_token(cfg) * rows * seq
            + flops.lm_attention_flops(cfg, window["pairs"]) / max(window["rows"], 1) * rows),
    )
