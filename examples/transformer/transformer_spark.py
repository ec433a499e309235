"""Transformer LM training on a cluster — the beyond-parity flagship.

The reference's model zoo stopped at CNNs (ResNet/U-Net/MNIST — SURVEY.md §5
"Long-context / sequence parallelism: absent"); this driver exercises the
TPU-native capabilities the framework adds on top of reference parity:

* flash attention (pallas, `ops/flash_attention.py`) via ``attention=auto``;
* sequence parallelism (`--mesh sp=2 ...` → ring attention over the ``sp``
  axis) for long context;
* tensor parallelism (``--mesh tp=...``, `_TP_RULES` param placement);
* rematerialization (``--remat``) trading FLOPs for HBM;
* any registered LM from a configuration file: ``--model <name>
  --model_config <file.json>`` builds the model through
  ``models.get_model`` from the file's keys (the size flags above are then
  unused) and trains it through the same pipeline, strategy and
  checkpoints. ``--model decoder`` is the per-layer-plan decoder
  (``models/decoder.py``: latent attention, routed + shared experts, of
  which a chip may hold a share, hyper-connected residual streams); its
  file holds the published ``config.json``'s keys, and
  ``examples/transformer/decoder_toy.json`` is one at toy widths. A file
  whose ``objective`` is ``block_diffusion``
  (``examples/transformer/sdar_toy.json``: grouped-query attention, softmax
  top-k experts) trains by diffusion over blocks: the text plane noises
  every packed row and the model reads the clean copy beside the noised one
  under the block-diffusion mask. A file with ``layer_types``
  (``examples/transformer/laguna_toy.json``: windowed layers of one head
  count among full ones of another, rotary by layer type, a sigmoid gate a
  head, a shared expert beside softmax top-k ones) trains its sliding layers
  under the attention's window rule, and the text plane counts their blocks
  apart (``flash_win_*``).

Data is real: TFRecord text shards stream through the sequence-packing
:class:`~tensorflowonspark_tpu.data.TextPipeline` (per-worker file shards,
FFD packing into ``[B, seq_len+1]`` with segment-id/position columns, the
packed-slab cache with ``--slab_cache_dir``). Without ``--data_dir`` a
deterministic synthetic corpus is materialized on the driver first — same
plumbing, zero setup.

Usage (single host):
    python examples/transformer/transformer_spark.py --train_steps 50 \
        --d_model 512 --n_layers 4 --seq_len 1024
    # 8-way CPU test: --platform cpu --mesh dp=2,tp=2,sp=2
    # the plan-built decoder at toy widths:
    python examples/transformer/transformer_spark.py --model decoder \
        --model_config examples/transformer/decoder_toy.json --seq_len 128 \
        --tokenizer word --platform cpu
    # block diffusion (the objective is the configuration's):
    python examples/transformer/transformer_spark.py --model decoder \
        --model_config examples/transformer/sdar_toy.json --seq_len 128 \
        --tokenizer word --platform cpu
    # windowed layers among full ones (the layers' types are the configuration's):
    python examples/transformer/transformer_spark.py --model decoder \
        --model_config examples/transformer/laguna_toy.json --seq_len 128 \
        --tokenizer word --platform cpu
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

#: word list for the synthetic corpus — varied lengths so FFD has real work
_WORDS = (
    "the spark cluster streams tokenized text through shared memory slabs "
    "while accelerator meshes consume packed sequences of variable length "
    "records a distributed pipeline keeps every chip busy with deterministic "
    "batches and observability counters tracking efficiency"
).split()


def make_text_corpus(data_dir, num_shards=4, records_per_shard=512, seed=0):
    """Materialize a deterministic synthetic text corpus as TFRecord shards
    (raw UTF-8 records, the ``Tokenizer(field=None)`` shape). Record lengths
    are lognormal-ish so sequence packing has a realistic distribution to
    chew on. Idempotent: existing shards are reused."""
    import numpy as np

    from tensorflowonspark_tpu import tfrecord as tfr

    existing = tfr.list_shards(data_dir) if os.path.isdir(data_dir) else []
    if len(existing) >= num_shards:
        return existing
    os.makedirs(data_dir, exist_ok=True)
    rng = np.random.default_rng(seed)
    for s in range(num_shards):
        path = os.path.join(data_dir, "part-{:05d}".format(s))
        with tfr.TFRecordWriter(path) as w:
            for _ in range(records_per_shard):
                n = max(3, int(rng.lognormal(mean=3.0, sigma=0.6)))
                text = " ".join(rng.choice(_WORDS, size=n))
                w.write(text.encode("utf-8"))
    return tfr.list_shards(data_dir)


def parse_mesh(spec):
    """'dp=2,tp=2,sp=2' → {'dp': 2, 'tp': 2, 'sp': 2} (None: all-dp)."""
    if not spec:
        return None
    axes = {}
    for part in spec.split(","):
        name, _, size = part.partition("=")
        axes[name.strip()] = int(size)
    return axes


def main_fun(args, ctx, observer=None):
    """The jax child's training program. ``observer`` is ``chip_smoke.py``'s
    probe: it is shown the trainer once it is built
    (``observer.built(mesh, state, step_fn)``) and every dispatched step
    (``observer.step(i, batch, metrics)``) — the run itself is the same."""
    import time

    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu import models, parallel
    from tensorflowonspark_tpu.models import decoder, transformer
    from tensorflowonspark_tpu.train import SyncDataParallel, checkpoint

    ctx.initialize_distributed()
    axes = parse_mesh(args.mesh) or {"dp": -1}
    mesh = parallel.local_mesh(axes) if ctx.num_processes == 1 else ctx.mesh(axes)
    if getattr(args, "model_cfg", None):
        # a registered model from its configuration file (read on the driver)
        model = models.get_model(args.model, mesh=mesh, **dict(
            args.model_cfg, dtype=args.dtype, remat=args.remat, attention=args.attention))
        args.vocab_size = model.cfg.vocab_size
    else:
        model = transformer.create_model(
            mesh=mesh,
            vocab_size=args.vocab_size, d_model=args.d_model,
            n_layers=args.n_layers, n_heads=args.n_heads, d_ff=args.d_ff,
            max_seq_len=args.seq_len, dtype=args.dtype, remat=args.remat,
            attention=args.attention,
        )
    param_specs = decoder.make_param_specs(model) if isinstance(model, decoder.Decoder) else transformer.param_specs
    strategy = SyncDataParallel(mesh, param_spec_fn=param_specs if "tp" in mesh.axis_names else None)
    optimizer = optax.adamw(args.learning_rate)
    state = strategy.create_state(
        transformer.make_init_fn(model, sample_len=8), optimizer, jax.random.PRNGKey(0)
    )
    loss_fn = transformer.make_loss_fn(model)
    start_step = 0
    if args.model_dir:
        # resume contract (run_with_recovery / job resubmission): continue
        # from the newest checkpoint; sharded target = shard-direct restore
        latest = checkpoint.latest_checkpoint(args.model_dir)
        if latest:
            state = checkpoint.restore_checkpoint(latest, target=state)
            start_step = int(jax.device_get(state.step))
            print("resuming from {} at step {}".format(latest, start_step))
    steps_per_loop = max(args.steps_per_loop, 1)
    if steps_per_loop > 1:
        run = strategy.compile_train_loop(
            loss_fn, optimizer, steps_per_loop, has_aux=True
        )
    else:
        run = strategy.compile_train_step(loss_fn, optimizer, has_aux=True)

    # real corpus: per-worker TFRecord text shards → tokenize → FFD-pack
    # into [B, seq_len+1] (the +1 feeds the shift-by-one LM loss), with
    # segment_ids/positions fencing packed sequences in the attention mask
    from tensorflowonspark_tpu import obs
    from tensorflowonspark_tpu import tfrecord as tfr
    from tensorflowonspark_tpu.data import TextPipeline, Tokenizer, shard_files

    all_files = tfr.list_shards(args.data_dir)
    files = shard_files(all_files, ctx.num_workers, ctx.executor_id)
    if not files:
        # fail loudly NOW: a worker with no data would sit out the
        # collective train steps and hang the whole world at step 1
        raise RuntimeError(
            "worker {} got 0 of {} shard files in {} — distributed training "
            "needs at least num_workers ({}) shard files".format(
                ctx.executor_id, len(all_files), args.data_dir, ctx.num_workers
            )
        )
    # a block-diffusion model's mask id is noise, not a token: the tokenizer draws from the ids below it
    diffusion = getattr(model.cfg, "objective", "next_token") == "block_diffusion"
    tokenizer = Tokenizer(
        kind=args.tokenizer,
        vocab_size=(model.cfg.mask_id if diffusion else args.vocab_size) if args.tokenizer == "word" else None,
    )
    if tokenizer.vocab_size > args.vocab_size:
        raise ValueError(
            "model vocab_size {} smaller than tokenizer vocab {}".format(
                args.vocab_size, tokenizer.vocab_size
            )
        )
    pipe = TextPipeline(
        # next-token rows carry one more column than the model reads (the shift); block diffusion has no shift
        files, tokenizer, seq_len=args.seq_len + (0 if diffusion else 1), batch_size=args.batch_size,
        seed=ctx.executor_id, epochs=None, max_bad_records=args.max_bad_records,
        pack_workers=args.pack_workers, slab_cache_dir=args.slab_cache_dir,
        block_diffusion={"block_length": model.cfg.block_length, "mask_id": model.cfg.mask_id} if diffusion else None,
        # a model with windowed layers has the text plane count their attention blocks beside the full layers'
        attention_window=getattr(model.cfg, "sliding_window", None),
        # one with state-space layers has it count what their scans walk and restart on
        scan_restarts=any(layer[0] in ("mamba", "mamba2") for layer in getattr(model.cfg, "plan", ())),
    )
    stream = iter(pipe)

    def packed_batches():
        for batch in stream:
            yield strategy.shard_batch(batch)

    batches = packed_batches()
    if observer is not None:
        observer.built(mesh, state, run)
    t0, metrics = time.perf_counter(), {}
    i = start_step
    while i < args.train_steps:
        if steps_per_loop > 1 and i + steps_per_loop <= args.train_steps:
            batch = [next(batches) for _ in range(steps_per_loop)]
            i += steps_per_loop
        else:
            batch = next(batches)
            i += 1
        state, metrics = run(state, batch)
        if observer is not None:
            observer.step(i, batch, metrics)
        if i % args.log_steps == 0 or i >= args.train_steps:
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            tps = args.batch_size * args.seq_len * (i - start_step) / dt
            print("step {}: loss {:.3f} ({:.0f} tokens/s)".format(
                i, float(metrics["loss"]), tps))
    stream.close()  # stop the producer (and the pack plane) before teardown
    if steps_per_loop == 1:
        run.drain()  # book what the model counted in the last steps
    if args.model_dir and (ctx.distributed or ctx.executor_id == 0):
        checkpoint.save_checkpoint(
            os.path.join(args.model_dir, "ckpt_{}".format(args.train_steps)),
            jax.device_get(state),
        )
    print(
        "transformer training complete: mesh={} packing_efficiency={:.3f}".format(
            dict(zip(mesh.axis_names, mesh.devices.shape)),
            obs.gauge("text_pack_efficiency").value,
        )
    )


def build_parser():
    parser = argparse.ArgumentParser()
    parser.add_argument("--attention", default="auto",
                        help="auto (flash kernel on TPU, ring over sp, plain elsewhere), flash, flash_interpret, plain or ring")
    parser.add_argument("--batch_size", type=int, default=8)
    parser.add_argument("--cluster_size", type=int, default=None,
                        help="explicit cluster size (default: from the Spark conf/parallelism under Spark; 1 on the local backend)")
    parser.add_argument("--d_ff", type=int, default=1024)
    parser.add_argument("--d_model", type=int, default=256)
    parser.add_argument("--data_dir", default=None,
                        help="TFRecord text shards (raw UTF-8 records); default: a deterministic synthetic corpus materialized on the driver")
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--learning_rate", type=float, default=3e-4)
    parser.add_argument("--log_steps", type=int, default=10)
    parser.add_argument("--max_bad_records", type=int, default=0)
    parser.add_argument("--mesh", default=None,
                        help="e.g. dp=2,tp=2,sp=2 (default: all-dp)")
    parser.add_argument("--model", default="transformer",
                        help="a registered LM (models.get_model): transformer, or with --model_config decoder")
    parser.add_argument("--model_config", default=None,
                        help="JSON file of the model's configuration keys; replaces the size flags")
    parser.add_argument("--model_dir", default=None)
    parser.add_argument("--n_heads", type=int, default=8)
    parser.add_argument("--n_layers", type=int, default=2)
    parser.add_argument("--pack_workers", type=int, default=0,
                        help="0 = in-process thread packing, N = forked pack-plane workers")
    parser.add_argument("--platform", default=None,
                        help="tpu to demand the chip, cpu for a test run; unset, the jax child inherits JAX_PLATFORMS")
    parser.add_argument("--remat", action="store_true")
    parser.add_argument("--seq_len", type=int, default=256)
    parser.add_argument("--slab_cache_dir", default=None,
                        help="packed-slab cache root (epoch >= 2 serves token rows from a memory map)")
    parser.add_argument("--steps_per_loop", type=int, default=1)
    parser.add_argument("--tokenizer", default="byte", choices=("byte", "word"))
    parser.add_argument("--train_steps", type=int, default=20)
    parser.add_argument("--vocab_size", type=int, default=1024)
    return parser


def main(argv=None, sc=None):
    args = build_parser().parse_args(argv)
    args.model_cfg = None
    if args.model_config:
        import json

        with open(args.model_config) as f:
            args.model_cfg = json.load(f)
    elif args.model != "transformer":
        raise SystemExit("--model {} needs --model_config".format(args.model))

    if not args.data_dir:
        args.data_dir = os.path.join("/tmp", "tos_transformer_corpus")
        shards = make_text_corpus(args.data_dir)
        print("synthetic text corpus: {} shards in {}".format(len(shards), args.data_dir))

    from tensorflowonspark_tpu import TFCluster

    from tensorflowonspark_tpu.backends import get_spark_context

    # spark-submit / pyspark when present, local backend otherwise;
    # a caller-supplied sc is passed through with owned=False
    sc, args.cluster_size, owned = get_spark_context("transformer_spark", args.cluster_size, sc=sc, local_default=1)
    env = {"JAX_PLATFORMS": args.platform} if args.platform else None
    if args.platform == "cpu" and args.mesh:
        # expose enough virtual devices for the requested mesh
        n = 1
        for v in parse_mesh(args.mesh).values():
            n *= max(v, 1)
        env["TOS_NUM_CPU_DEVICES"] = str(n)
    try:
        cluster = TFCluster.run(
            sc, main_fun, args, args.cluster_size,
            input_mode=TFCluster.InputMode.TENSORFLOW, master_node="chief", env=env,
        )
        cluster.shutdown()
        print("transformer run complete")
    finally:
        if owned:
            sc.stop()


if __name__ == "__main__":
    from tensorflowonspark_tpu import util

    util.setup_logging()
    main()
