"""The ``moe_lm`` family: packed-document training of the plan-built decoder
(``tensorflowonspark_tpu/models/decoder.py``: latent attention, routed +
shared experts of which this chip holds a share, hyper-connected residual
streams), built from the program's public entry points in the order
``examples/transformer/transformer_spark.py --model decoder --model_config``
calls them. The benchmark's child (``child.py``) owns the loop, the window
and the spans; this file builds what it drives.

Unlike ``families/lm.py`` the corpus and the pipeline's shuffle are seeded by
the traffic file's own ``corpus.seed``: which documents share a row decides
how many attention blocks a step computes, and a rate that moves with the
packing would spread over the cell's bound (PERF.md §6, PR 25). ``--seed``
draws the weights and the selection bias, and the bias is then balanced on
the first batch by its own rule (``reference/moe_lm.balanced_bias``, before
the program's state takes the chip's memory): a routed layer's work follows
its routing, and under a seeded bias the held experts' share of the slots ran
from 9% to 21% across seeds. The router's matrix is then left where it is, as
fine-tuning recipes for routed models leave it (the configuration's
``optimizer.frozen``): trained at the other weights' rate it loses the balance
again within the window (12% to 21% held from a balanced start, the rate
spreading 0.72% over six seeds; my chip runs, PR 26).

``pack_ahead`` is the text plane's own knob (``TextPipeline(pack_ahead=)``):
the packer's window holds that many batches of tokens, and at one
row a batch its default of 2 leaves a third of every row empty (66.9% real
tokens, my chip run, PR 26), where the dense LM's four rows of 4096 pack to
99.8% at the default.
"""

import os
import time

import numpy as np

from benchmarks import corpus, flops, flops_moe_lm
from benchmarks.families import common
from benchmarks.reference import moe_lm as reference

#: keys of the cell's configuration file that are the benchmark's own; every
#: other key is the model's and goes to the program, which refuses what it
#: does not know
BENCHMARK_KEYS = (
    "family", "source", "why", "router_experts", "param_dtype", "optimizer", "deployment", "parameters",
    "source_config", "reduced", "reduced_why", "assumed",
)


def model_config(cfg, remat):
    """What ``--model_config`` would hold: the configuration's model keys,
    with the router as wide as the model's (``router_experts``; the file's
    ``n_routed_experts`` counts the experts held here, as the model-configs
    guide asks, and ``experts_held`` names them)."""
    assert cfg["n_routed_experts"] == cfg["experts_held"][1]
    model = {k: v for k, v in cfg.items() if k not in BENCHMARK_KEYS}
    model.update(n_routed_experts=cfg["router_experts"], remat=remat)
    return model


def frozen(name, opt):
    """Whether the optimizer leaves the parameter ``name`` (``a/b/c``) where
    it is: it ends with one of the configuration's ``optimizer.frozen``."""
    return any(name.endswith(end) for end in opt["frozen"])


def make_optimizer(opt):
    """``common.make_optimizer``'s, with the updates of the ``frozen``
    parameters set to zero after it (their gradient still reaches the
    moments, where the comparison reads it)."""
    import jax
    import optax

    tx, first_gradient = common.make_optimizer(opt)

    def mask(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: frozen("/".join(str(p.key) for p in path), opt), params)

    return optax.chain(tx, optax.masked(optax.set_to_zero(), mask)), lambda state: first_gradient(state[0])


def build(spec, ctx, parts):
    import itertools

    import jax

    from tensorflowonspark_tpu import models, obs, parallel
    from tensorflowonspark_tpu import tfrecord as tfr
    from tensorflowonspark_tpu.data import TextPipeline, Tokenizer, shard_files
    from tensorflowonspark_tpu.models import transformer
    from tensorflowonspark_tpu.train import SyncDataParallel

    cfg, traffic, seed = spec["config"], spec["traffic"], spec["seed"]
    assert ctx.num_processes == 1, "the selection bias is balanced on this process's first batch"
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
    pipe = TextPipeline(
        files, tokenizer, seq_len=seq + 1, batch_size=rows, seed=traffic["corpus"]["seed"],
        epochs=None, pack_workers=traffic["pack_workers"], pack_ahead=traffic["pack_ahead"],
    )
    stream = iter(pipe)
    first = next(stream)
    key = common.seed_key(seed)
    t0 = time.perf_counter()
    bias = jax.device_get(jax.jit(lambda k, batch: reference.balanced_bias(k, cfg, batch))(
        key, {k: np.asarray(v) for k, v in first.items()}))
    parts["balance_s"] = time.perf_counter() - t0

    strategy = SyncDataParallel(mesh)
    optimizer, first_gradient = make_optimizer(cfg["optimizer"])
    t0 = time.perf_counter()
    # the seeded weights by a program every seed shares (the bias is data, not a constant of it)
    state = common.seeded_state(strategy, optimizer, lambda k: {"params": reference.init_params(k, cfg)}, key)
    params = jax.tree.map(lambda x: x, state.params)
    for layer, b in bias.items():
        params[layer]["moe"]["router_bias"] = jax.device_put(b, params[layer]["moe"]["router_bias"].sharding)
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
            seg = batch["segment_ids"][:, :-1]
            counts["rows"] += seg.shape[0]
            counts["real_tokens"] += int((seg > 0).sum())
            counts["pairs"] += flops.causal_pairs(seg)
            # what the program has booked so far of its routed layers' counts
            # (TrainStep books a step once it has seen it finished)
            counts["slots_routed"], counts["slots_held"] = routed_total.value, held_total.value
            if len(kept) < traffic["check_steps"]:
                kept.append({k: np.array(v) for k, v in batch.items()})
            yield strategy.shard_batch(batch)

    first_grad, _ = common.norm_readers(first_gradient, None, key)
    change_norms = jax.jit(lambda params, k, b: common.leaf_norms(params, reference.init_params(k, cfg, b)))

    def param_change(state):
        return common.to_floats(change_norms(state.params, key, bias))

    slots_per_step = rows * seq * cfg["num_experts_per_tok"] * flops_moe_lm.layers(cfg)[1]

    def flops_per_step(window):
        held = window["slots_held"] / window["slots_routed"] * slots_per_step if window.get("slots_routed") else 0.0
        return (
            flops_moe_lm.matmul_flops(cfg, rows * seq, held)
            + flops_moe_lm.attention_flops(cfg, window["pairs"]) / max(window["rows"], 1) * rows)

    def close():
        stream.close()
        step.drain()

    return common.job(
        state=state, step=step, batches=packed_batches(), close=close,
        mesh=mesh, chips=chips, unit="tokens", units_per_step=rows * seq,
        counts=counts, kept=kept, corpus=made,
        first_grad=first_grad, param_change=param_change,
        reference=lambda batches, quant=None: reference.follow(
            cfg, key, batches, list(mesh.devices.flat), quant=quant, router_bias=bias),
        flops_per_step=flops_per_step,
    )
