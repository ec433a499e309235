"""The ``bd_lm`` family: block-diffusion training on packed documents of the
plan-built decoder (``tensorflowonspark_tpu/models/decoder.py``:
grouped-query attention, softmax-routed experts of which this chip holds a
share), built from the program's public entry points in the order
``examples/transformer/transformer_spark.py --model decoder --model_config``
calls them. The benchmark's child (``child.py``) owns the loop, the window
and the spans; this file builds what it drives.

The text plane packs **and noises** (``TextPipeline(block_diffusion=)``),
both seeded by the traffic file's ``corpus.seed``: which documents share a
row decides how many attention blocks a step computes, and which positions
are masked decides what the routers see. ``--seed`` draws every weight. The
routers' matrices are then calibrated on the first batch
(``reference/bd_lm.calibrated_routers``, before the program's state takes the
chip's memory) so that the experts held here receive their even share of the
slots, and left where they are (the configuration's ``optimizer.frozen``), as
``families/moe_lm.py`` treats its selection bias and routers, and for the same
reason: a routed layer's work follows its routing.

A unit of the rate is a **data token**: ``rows x seq_len`` a step. The model
reads twice that many positions (the clean copy beside the noised one).
"""

import itertools
import os
import time

import numpy as np

from benchmarks import corpus, flops_bd_lm
from benchmarks.families import common
from benchmarks.families.moe_lm import make_optimizer
from benchmarks.reference import bd_lm as reference

#: keys of the cell's configuration file that are the benchmark's own; every
#: other key is the model's and goes to the program, which refuses what it
#: does not know
BENCHMARK_KEYS = (
    "family", "source", "why", "router_experts", "param_dtype", "optimizer", "deployment", "parameters",
    "source_config", "reduced", "reduced_why", "assumed", "noise",
)


def model_config(cfg, remat):
    """What ``--model_config`` would hold: the configuration's model keys,
    with the router as wide as the model's (``router_experts``; the file's
    ``num_experts`` counts the experts held here, as the model-configs guide
    asks, and ``experts_held`` names them)."""
    assert cfg["num_experts"] == cfg["experts_held"][1]
    model = {k: v for k, v in cfg.items() if k not in BENCHMARK_KEYS}
    model.update(num_experts=cfg["router_experts"], remat=remat)
    return model


def noising(cfg):
    """``TextPipeline(block_diffusion=)``'s argument from the configuration."""
    return {"block_length": cfg["block_length"], "mask_id": cfg["mask_token_id"], "t_min": cfg["noise"]["t_min"]}


def build(spec, ctx, parts):
    import jax

    from tensorflowonspark_tpu import models, obs, parallel
    from tensorflowonspark_tpu import tfrecord as tfr
    from tensorflowonspark_tpu.data import TextPipeline, Tokenizer, shard_files
    from tensorflowonspark_tpu.models import transformer
    from tensorflowonspark_tpu.train import SyncDataParallel

    cfg, traffic, seed = spec["config"], spec["traffic"], spec["seed"]
    assert ctx.num_processes == 1, "the routers are calibrated on this process's first batch"
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
    # the mask id is the slice's last: the tokenizer draws from the ids below it
    tokenizer = Tokenizer(kind=traffic["tokenizer"], vocab_size=cfg["mask_token_id"])
    pipe = TextPipeline(
        files, tokenizer, seq_len=seq, batch_size=rows, seed=traffic["corpus"]["seed"],
        epochs=None, pack_workers=traffic["pack_workers"], pack_ahead=traffic["pack_ahead"],
        block_diffusion=noising(cfg),
    )
    stream = iter(pipe)
    first = next(stream)
    key = common.seed_key(seed)
    t0 = time.perf_counter()
    routers = jax.device_get(jax.jit(lambda k, batch: reference.calibrated_routers(k, cfg, batch))(
        key, {k: np.asarray(v) for k, v in first.items()}))
    parts["balance_s"] = time.perf_counter() - t0

    strategy = SyncDataParallel(mesh)
    optimizer, first_gradient = make_optimizer(cfg["optimizer"])
    t0 = time.perf_counter()
    # the seeded weights by a program every seed shares (the routers are data, not a constant of it)
    state = common.seeded_state(strategy, optimizer, lambda k: {"params": reference.init_params(k, cfg)}, key)
    params = jax.tree.map(lambda x: x, state.params)
    for layer, router in routers.items():
        params[layer]["moe"]["router"] = jax.device_put(router, params[layer]["moe"]["router"].sharding)
    state = state.replace(params=params)
    jax.block_until_ready(state.params)
    parts["state_s"] = time.perf_counter() - t0
    step = strategy.compile_train_step(transformer.make_loss_fn(model), optimizer, has_aux=True)

    counts = {"rows": 0, "real_tokens": 0, "pairs": 0, "slots_routed": 0.0, "slots_held": 0.0}
    routed_total = obs.counter("moe_slots_routed_total")
    held_total = obs.counter("moe_slots_held_total")
    kept = []

    def packed_batches():
        for batch in itertools.chain([first], stream):
            seg = batch["segment_ids"]
            counts["rows"] += seg.shape[0]
            counts["real_tokens"] += int((seg > 0).sum())
            counts["pairs"] += flops_bd_lm.visible_pairs(seg, batch["positions"], cfg["block_length"])
            # what the program has booked so far of its routed layers' counts
            # (TrainStep books a step once it has seen it finished)
            counts["slots_routed"], counts["slots_held"] = routed_total.value, held_total.value
            if len(kept) < traffic["check_steps"]:
                kept.append({k: np.array(v) for k, v in batch.items()})
            yield strategy.shard_batch(batch)

    first_grad, _ = common.norm_readers(first_gradient, None, key)
    change_norms = jax.jit(lambda params, k, r: common.leaf_norms(params, reference.init_params(k, cfg, r)))

    def param_change(state):
        return common.to_floats(change_norms(state.params, key, routers))

    slots_per_step = flops_bd_lm.slots_per_step(cfg, rows, seq)

    def flops_per_step(window):
        held = window["slots_held"] / window["slots_routed"] * slots_per_step if window.get("slots_routed") else 0.0
        return (
            flops_bd_lm.matmul_flops(cfg, rows * seq, held)
            + flops_bd_lm.attention_flops(cfg, window["pairs"]) / max(window["rows"], 1) * rows)

    def close():
        stream.close()
        step.drain()

    return common.job(
        state=state, step=step, batches=packed_batches(), close=close,
        mesh=mesh, chips=chips, unit="tokens", units_per_step=rows * seq,
        counts=counts, kept=kept, corpus=made,
        first_grad=first_grad, param_change=param_change,
        reference=lambda batches, quant=None: reference.follow(
            cfg, key, batches, list(mesh.devices.flat), quant=quant, routers=routers),
        flops_per_step=flops_per_step,
    )
