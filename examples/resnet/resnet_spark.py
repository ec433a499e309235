"""ResNet training on a cluster — the performance workload.

Parity with /root/reference/examples/resnet/resnet_cifar_spark.py +
resnet_imagenet_main.py: ``--dataset cifar`` trains ResNet-56 (batch 128,
piecewise LR like resnet_cifar_dist.py:34-36), ``--dataset imagenet`` trains
ResNet-50 v1.5 (base LR 0.1·bs/256 with warmup like
resnet_imagenet_main.py:37-71). bf16 compute replaces the reference's
fp16+LossScaleOptimizer.

Input paths, matching the reference's two modes:
* ``--data_dir <tfrecords>`` — REAL data: TFRecord shards read through the
  framework input pipeline (tensorflowonspark_tpu.data: native bulk reads,
  threaded decode/crop/flip/normalize, per-worker file sharding, device
  double-buffering — the imagenet_preprocessing.py:259 input_fn analogue).
* ``--use_synthetic_data`` — the reference's synthetic path (common.py:315),
  default when no --data_dir is given.

Usage:
    python examples/resnet/resnet_spark.py --dataset cifar --train_steps 100 \
        --data_dir /data/cifar_tfrecords

Under spark-submit the same script runs on a real cluster unchanged
(context + executor count resolve via backends.get_spark_context):

    spark-submit --master $MASTER --conf spark.executor.instances=N \
        examples/resnet/resnet_spark.py [args...]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def lr_schedule(args):
    """Reference schedules: piecewise for CIFAR, warmup+scaled for ImageNet."""
    import optax

    if args.dataset == "cifar":
        # (0.1, 91ep) (0.01, 136ep) (0.001, 182ep) — in steps
        spe = max(args.steps_per_epoch, 1)
        return optax.piecewise_constant_schedule(
            0.1, {91 * spe: 0.1, 136 * spe: 0.1}
        )
    base = 0.1 * args.batch_size / 256.0
    warmup = 5 * max(args.steps_per_epoch, 1)
    return optax.linear_schedule(0.0, base, warmup)


def main_fun(args, ctx):
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu import parallel
    from tensorflowonspark_tpu.models import resnet
    from tensorflowonspark_tpu.train import SyncDataParallel

    ctx.initialize_distributed()
    mesh = parallel.local_mesh({"dp": -1}) if ctx.num_processes == 1 else ctx.mesh({"dp": -1})
    strategy = SyncDataParallel(mesh)
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    if args.dataset == "cifar":
        model, image_size, classes = resnet.resnet56(dtype=dtype), 32, 10
    else:
        model, image_size, classes = resnet.resnet50(dtype=dtype), 224, 1000
    if args.image_size:
        image_size = args.image_size
    use_real = bool(args.data_dir) and not args.use_synthetic_data
    # imagenet real data feeds raw uint8 (quarter the host->device bytes);
    # the mean subtraction fuses into the first conv on device
    feed_uint8 = use_real and args.dataset == "imagenet"
    optimizer = optax.sgd(lr_schedule(args), momentum=0.9)
    state = strategy.create_state(
        resnet.make_init_fn(model, image_size=image_size), optimizer, jax.random.PRNGKey(0)
    )
    from tensorflowonspark_tpu.data import imagenet as imagenet_mod

    loss_fn = resnet.make_loss_fn(
        model, weight_decay=1e-4,
        normalize=imagenet_mod.device_normalize if feed_uint8 else None,
    )
    # distributed worlds: EVERY process must join the (collective) save;
    # independent workers: only the chief writes, or they race on the dir
    is_saver = ctx.distributed or ctx.job_name in ("chief", "master") or ctx.num_workers <= 1
    start_step = 0
    from tensorflowonspark_tpu.train import checkpoint

    if args.model_dir:
        latest = checkpoint.latest_checkpoint(args.model_dir)
        if latest:
            # the crash→relaunch contract (TFCluster.run_with_recovery and
            # plain job resubmission both land here): pick up the trajectory
            # at the newest checkpoint instead of step 0. The live sharded
            # state is the restore target, so orbax restores each shard
            # straight onto its mesh device — no full-array host round trip
            state = checkpoint.restore_checkpoint(latest, target=state)
            start_step = int(jax.device_get(state.step))
            print("resuming from {} at step {}".format(latest, start_step))
    steps_per_loop = max(int(getattr(args, "steps_per_loop", 1) or 1), 1)
    if steps_per_loop > 1:
        # K steps fused into one lax.scan dispatch; transfers overlap compute.
        # The state alone is donated, which is safe for the synthetic path's
        # re-fed device batch too.
        loop = strategy.compile_train_loop(loss_fn, optimizer, steps_per_loop, mutable=True)
    step = strategy.compile_train_step(loss_fn, optimizer, mutable=True)

    if use_real:
        # REAL data: per-worker file shards → threaded decode/augment →
        # device double-buffering (InputMode.TENSORFLOW per-worker sharding,
        # reference mnist_inference.py:42 ds.shard + input_fn)
        from tensorflowonspark_tpu import tfrecord as tfr
        from tensorflowonspark_tpu.data import ImagePipeline, device_prefetch, shard_files
        from tensorflowonspark_tpu.data import cifar as cifar_data
        from tensorflowonspark_tpu.data import imagenet as imagenet_data

        all_files = tfr.list_shards(args.data_dir)
        files = shard_files(all_files, ctx.num_workers, ctx.executor_id)
        if not files:
            # fail loudly NOW: a worker with no data would sit out the
            # collective train steps and hang the whole world at step 1
            raise RuntimeError(
                "worker {} got 0 of {} shard files in {} — distributed "
                "training needs at least num_workers ({}) shard files".format(
                    ctx.executor_id, len(all_files), args.data_dir, ctx.num_workers
                )
            )
        parse = (
            cifar_data.make_parse_fn(True, seed=ctx.executor_id)
            if args.dataset == "cifar"
            else imagenet_data.make_parse_fn(
                True, image_size=image_size, label_offset=args.label_offset,
                seed=ctx.executor_id, raw_uint8=feed_uint8,
            )
        )
        pipe = ImagePipeline(
            files, parse, args.batch_size, seed=ctx.executor_id, epochs=None,
            num_threads=args.data_threads,
        )
        batches = device_prefetch(pipe, strategy)
    else:
        rng = np.random.default_rng(ctx.executor_id)
        synthetic = strategy.shard_batch(
            {
                "image": rng.standard_normal((args.batch_size, image_size, image_size, 3)).astype(np.float32),
                "label": rng.integers(0, classes, args.batch_size),
            }
        )
        batches = iter(lambda: synthetic, None)  # repeat forever

    profile_range = None
    if args.profile_steps:
        # reference: --profile_steps -> profiler callback over a step range
        # (common.py:192-197); here the jax profiler traces the same range
        lo, _, hi = args.profile_steps.partition(",")
        profile_range = (int(lo), int(hi or lo))

    t0, metrics = time.perf_counter(), {}
    i = last_log = last_ckpt = start_step
    profiling = False
    while i < args.train_steps:
        if profile_range and not profiling and i >= profile_range[0]:
            trace_dir = os.path.join(args.model_dir or ".", "profile")
            jax.profiler.start_trace(trace_dir)
            profiling = True
        if steps_per_loop > 1 and i + steps_per_loop <= args.train_steps:
            state, metrics = loop(state, [next(batches) for _ in range(steps_per_loop)])
            i += steps_per_loop
        else:
            state, metrics = step(state, next(batches))
            i += 1
        if profiling and i >= profile_range[1]:
            jax.block_until_ready(metrics["loss"])
            jax.profiler.stop_trace()
            profiling = False
            profile_range = None  # captured once; never re-trigger
            print("profiler trace written to {}".format(trace_dir))
        if args.model_dir and args.checkpoint_steps and is_saver and (
            i - last_ckpt >= args.checkpoint_steps
        ):
            jax.block_until_ready(metrics["loss"])
            checkpoint.save_checkpoint(
                os.path.join(args.model_dir, "ckpt_{}".format(i)), jax.device_get(state)
            )
            last_ckpt = i
            checkpoint.prune_checkpoints(args.model_dir, args.keep_checkpoints)
        if i - last_log >= args.log_steps:
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            # avg_exp_per_second analogue (reference common.py:241-244)
            print("step {}: loss {:.3f} {:.1f} img/s".format(
                i, float(metrics["loss"]), args.batch_size * (i - last_log) / dt))
            last_log, t0 = i, time.perf_counter()
    if profiling:
        # a stop boundary past train_steps must still flush the trace
        jax.block_until_ready(metrics["loss"])
        jax.profiler.stop_trace()
        print("profiler trace written to {}".format(trace_dir))
    if metrics:
        jax.block_until_ready(metrics["loss"])
        print("final loss {:.3f}".format(float(metrics["loss"])))
        if args.model_dir and is_saver and last_ckpt < args.train_steps:
            checkpoint.save_checkpoint(
                os.path.join(args.model_dir, "ckpt_{}".format(args.train_steps)),
                jax.device_get(state),
            )

    if args.eval_dir and ctx.executor_id == 0:
        # the reference's per-run top-1 eval (resnet_imagenet_main.py):
        # aspect-preserving resize + central crop, no augmentation. Runs on
        # the FIRST worker only, over ALL eval shards, with host-gathered
        # params and no mesh: eval must not enter collectives (uneven
        # per-worker shard counts would hang the world) and must score every
        # example (drop_remainder=False keeps the short final batch).
        from tensorflowonspark_tpu import tfrecord as tfr
        from tensorflowonspark_tpu.data import ImagePipeline
        from tensorflowonspark_tpu.data import cifar as cifar_data
        from tensorflowonspark_tpu.data import imagenet as imagenet_data

        eval_files = tfr.list_shards(args.eval_dir)
        parse = (
            cifar_data.make_parse_fn(False)
            if args.dataset == "cifar"
            else imagenet_data.make_parse_fn(
                False, image_size=image_size, label_offset=args.label_offset,
                raw_uint8=feed_uint8,
            )
        )
        eval_fn = jax.jit(resnet.make_eval_fn(
            model, normalize=imagenet_mod.device_normalize if feed_uint8 else None
        ))
        params_host = jax.device_get(state.params)
        model_state_host = jax.device_get(state.model_state)
        correct = total = 0
        pipe = ImagePipeline(
            eval_files, parse, args.batch_size, shuffle=False, epochs=1,
            drop_remainder=False,
        )
        for b in pipe:
            c, n = eval_fn(params_host, model_state_host, b)
            correct += int(jax.device_get(c))
            total += int(n)
        if total:
            print("eval accuracy {:.4f} ({} examples)".format(correct / total, total))


def main(argv=None, sc=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch_size", type=int, default=128)
    parser.add_argument("--cluster_size", type=int, default=None,
                        help="explicit cluster size (default: from the Spark conf/parallelism under Spark; 1 on the local backend)")
    parser.add_argument("--data_dir", default=None, help="TFRecord shard dir (real-data mode)")
    parser.add_argument("--data_threads", type=int, default=8)
    parser.add_argument("--dataset", choices=["cifar", "imagenet"], default="cifar")
    parser.add_argument("--eval_dir", default=None,
                        help="TFRecord shard dir for post-training top-1 eval")
    parser.add_argument("--dtype", choices=["bf16", "fp32"], default="bf16")
    parser.add_argument("--image_size", type=int, default=None,
                        help="override the dataset's native size (tests/CI)")
    parser.add_argument("--label_offset", type=int, default=0,
                        help="-1 for 1-based ImageNet labels")
    parser.add_argument("--log_steps", type=int, default=20)
    parser.add_argument("--steps_per_loop", type=int, default=1,
                        help=">1 fuses that many train steps into one device "
                             "dispatch (lax.scan)")
    parser.add_argument("--model_dir", default=None)
    parser.add_argument("--profile_steps", default=None, metavar="START[,STOP]",
                        help="capture a jax profiler trace over this step range "
                             "(reference --profile_steps, common.py:192-197)")
    parser.add_argument("--steps_per_epoch", type=int, default=390)
    parser.add_argument("--train_steps", type=int, default=100)
    parser.add_argument("--use_synthetic_data", action="store_true", default=False,
                        help="force the synthetic path even when --data_dir is given; "
                             "synthetic is also the default when no --data_dir is set")
    parser.add_argument("--platform", default=None)
    parser.add_argument("--checkpoint_steps", type=int, default=0, metavar="N",
                        help="checkpoint every N steps into --model_dir "
                             "(0 = final checkpoint only)")
    parser.add_argument("--keep_checkpoints", type=int, default=5, metavar="K",
                        help="retain only the newest K periodic checkpoints")
    parser.add_argument("--auto_recover", type=int, default=0, metavar="N",
                        help="relaunch the cluster up to N times on node "
                             "failure, resuming from the latest checkpoint "
                             "(pair with --model_dir + --checkpoint_steps; "
                             "TFCluster.run_with_recovery)")
    args = parser.parse_args(argv)
    if args.auto_recover and not (args.model_dir and args.checkpoint_steps):
        # without a mid-run checkpoint to resume from, every relaunch would
        # silently restart at step 0 — refuse the misconfiguration up front
        parser.error("--auto_recover requires --model_dir and --checkpoint_steps")

    from tensorflowonspark_tpu import TFCluster

    from tensorflowonspark_tpu.backends import get_spark_context

    # spark-submit / pyspark when present, local backend otherwise;
    # a caller-supplied sc is passed through with owned=False
    sc, args.cluster_size, owned = get_spark_context("resnet_spark", args.cluster_size, sc=sc, local_default=1)
    env = {"JAX_PLATFORMS": args.platform} if args.platform else None
    try:
        if args.auto_recover:
            relaunches = TFCluster.run_with_recovery(
                sc, main_fun, args, args.cluster_size,
                max_relaunches=args.auto_recover,
                input_mode=TFCluster.InputMode.TENSORFLOW, master_node="chief",
                env=env,
            )
            print("resnet training complete ({} relaunch(es))".format(relaunches))
        else:
            cluster = TFCluster.run(
                sc, main_fun, args, args.cluster_size,
                input_mode=TFCluster.InputMode.TENSORFLOW, master_node="chief", env=env,
            )
            cluster.shutdown()
            print("resnet training complete")
    finally:
        if owned:
            sc.stop()


if __name__ == "__main__":
    from tensorflowonspark_tpu import util

    util.setup_logging()
    main()
