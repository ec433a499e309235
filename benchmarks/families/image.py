"""The ``image`` family: ResNet training from the JPEG input plane, built
from the program's public entry points in the order
``examples/resnet/resnet_spark.py --dataset imagenet`` calls them. The feed
is uint8, normalised on the device; ``slab_cache`` in the traffic file says
whether the decoded-slab cache is filled during set-up (``"warm"``) or off
(``"off"``: every batch is decoded from JPEG)."""

import os
import time

import numpy as np

from benchmarks import corpus, flops
from benchmarks.families import common
from benchmarks.reference import image as reference


def build(spec, ctx, parts):
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import parallel
    from tensorflowonspark_tpu import tfrecord as tfr
    from tensorflowonspark_tpu.data import ImagePipeline, device_prefetch, shard_files
    from tensorflowonspark_tpu.data import imagenet
    from tensorflowonspark_tpu.models import resnet
    from tensorflowonspark_tpu.train import SyncDataParallel

    cfg, traffic, seed = spec["config"], spec["traffic"], spec["seed"]
    ctx.initialize_distributed()
    axes = dict(traffic["mesh"])
    mesh = parallel.local_mesh(axes) if ctx.num_processes == 1 else ctx.mesh(axes)
    chips = int(mesh.devices.size)
    batch = traffic["batch_per_chip"] * chips

    t0 = time.perf_counter()
    data_dir = os.path.join(spec["scratch"], "corpus")
    made = corpus.make_jpeg(data_dir, traffic["corpus"], seed)
    parts["corpus_s"] = time.perf_counter() - t0

    strategy = SyncDataParallel(mesh)
    model = resnet.ResNet(
        stage_sizes=tuple(cfg["stage_sizes"]), filters=tuple(cfg["filters"]),
        num_classes=cfg["num_classes"], bottleneck=True, stem="imagenet", dtype=jnp.dtype(cfg["dtype"]),
    )
    optimizer, first_gradient = common.make_optimizer(cfg["optimizer"])
    key = common.seed_key(seed)
    t0 = time.perf_counter()
    state = common.seeded_state(strategy, optimizer, lambda k: reference.init_variables(k, cfg), key)
    jax.block_until_ready(state.params)
    parts["state_s"] = time.perf_counter() - t0
    loss_fn = resnet.make_loss_fn(model, weight_decay=cfg["weight_decay"], normalize=imagenet.device_normalize)
    step = strategy.compile_train_step(loss_fn, optimizer, mutable=True)

    files = shard_files(tfr.list_shards(data_dir), ctx.num_workers, ctx.executor_id)
    parse = imagenet.make_parse_fn(True, image_size=cfg["image_size"], seed=0, raw_uint8=True)
    slab_dir = None
    options = dict(num_threads=traffic["data_threads"], decode_workers=traffic["decode_workers"])
    if traffic["slab_cache"] == "warm":
        slab_dir = os.path.join(spec["scratch"], "slabs")
        t0 = time.perf_counter()
        for _ in ImagePipeline(files, parse, batch, seed=common.small_seed(seed), epochs=1,
                               slab_cache_dir=slab_dir, **options):
            pass
        parts["cache_fill_s"] = time.perf_counter() - t0
    elif traffic["slab_cache"] != "off":
        raise ValueError("slab_cache must be 'warm' or 'off', got {!r}".format(traffic["slab_cache"]))
    pipe = ImagePipeline(files, parse, batch, seed=common.small_seed(seed), epochs=None,
                         slab_cache_dir=slab_dir, **options)
    stream = iter(pipe)
    kept = []

    def host_batches():
        for b in stream:
            if len(kept) < traffic["check_steps"]:
                kept.append({k: np.array(v) for k, v in b.items()})
            yield b

    first_grad, param_change = common.norm_readers(
        first_gradient, lambda k: reference.init_variables(k, cfg)["params"], key)

    return common.job(
        state=state, step=step, batches=device_prefetch(host_batches(), strategy), close=stream.close,
        mesh=mesh, chips=chips, unit="images", units_per_step=batch,
        counts={}, kept=kept, corpus=made,
        first_grad=first_grad, param_change=param_change,
        reference=lambda batches, quant=None: reference.follow(
            cfg, key, batches, list(mesh.devices.flat), quant=quant),
        flops_per_step=lambda window: flops.resnet_flops_per_image(cfg) * batch,
    )
