"""The ``ssd_lm`` family: packed-document training of the plan-built decoder
(``tensorflowonspark_tpu/models/decoder.py``) in its one-sub-layer dialect
(``hybrid_override_pattern``) — Mamba-2 blocks scanned in chunked matrix form,
grouped-query attention blocks and latent mixture-of-experts blocks, of each a
chip's share (``heads_held``, ``experts_held``). Built from the program's
public entry points in the order
``examples/transformer/transformer_spark.py --model decoder --model_config``
calls them; the benchmark's child (``child.py``) owns the loop, the window and
the spans; this file builds what it drives.

As in ``families/ssm_lm.py`` the corpus and the packing are seeded by the
traffic file's ``corpus.seed`` (which documents share a row decides how many
attention blocks a step computes and where the scans restart), the text plane
is told that the model scans (``TextPipeline(scan_restarts=True)``) and counts
the restarts, and what the last ``trace_steps`` batches held is kept apart
from the window's sums (``parts["traced_*"]``). As in ``families/moe_lm.py``
``--seed`` draws the weights and the selection bias, the bias is then balanced
on the first batch by its own rule (``reference/ssd_lm.balanced_bias``, over
the real positions, before the program's state takes the chip's memory), and
the routers' matrices are left where they are (the configuration's
``optimizer.frozen``).
"""

import collections
import itertools
import os
import time

import numpy as np

from benchmarks import corpus, flops_ssd_lm
from benchmarks.families import common
from benchmarks.families.moe_lm import make_optimizer
from benchmarks.reference import ssd_lm as reference

#: keys of the cell's configuration file that are the benchmark's own; every
#: other key is the model's and goes to the program, which refuses what it
#: does not know
BENCHMARK_KEYS = (
    "family", "source", "why", "router_experts", "model_heads", "param_dtype", "optimizer", "deployment",
    "parameters", "source_config", "reduced", "reduced_why", "assumed",
)


def model_config(cfg, remat):
    """What ``--model_config`` would hold: the configuration's model keys,
    with the router and the mixers as wide as the model's (``router_experts``,
    ``model_heads``; the file's ``n_routed_experts``, ``mamba_num_heads``,
    ``n_groups``, ``num_attention_heads`` and ``num_key_value_heads`` count
    what is held here, as the model-configs guide asks, and ``experts_held``
    and ``heads_held`` name it)."""
    assert cfg["n_routed_experts"] == cfg["experts_held"][1]
    shares = cfg["heads_held"][1]
    for key, whole in cfg["model_heads"].items():
        assert cfg[key] == max(whole // shares, 1), key
    model = {k: v for k, v in cfg.items() if k not in BENCHMARK_KEYS}
    model.update(cfg["model_heads"], n_routed_experts=cfg["router_experts"], remat=remat)
    return model


def build(spec, ctx, parts):
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
        epochs=None, pack_workers=traffic["pack_workers"], pack_ahead=traffic["pack_ahead"], scan_restarts=True,
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
    last = collections.deque(maxlen=traffic["trace_steps"])

    def packed_batches():
        for batch in itertools.chain([first], stream):
            seg = batch["segment_ids"][:, :-1]
            pairs = flops_ssd_lm.visible_pairs(seg)
            counts["rows"] += seg.shape[0]
            counts["real_tokens"] += int((seg > 0).sum())
            counts["pairs"] += pairs
            last.append((seg.shape[0], pairs))
            parts["traced_rows"], parts["traced_pairs"] = map(sum, zip(*last))
            # what the program has booked so far of its routed blocks' counts
            # (TrainStep books a step once it has seen it finished)
            counts["slots_routed"], counts["slots_held"] = routed_total.value, held_total.value
            if len(kept) < traffic["check_steps"]:
                kept.append({k: np.array(v) for k, v in batch.items()})
            yield strategy.shard_batch(batch)

    first_grad, _ = common.norm_readers(first_gradient, None, key)
    change_norms = jax.jit(lambda params, k, b: common.leaf_norms(params, reference.init_params(k, cfg, b)))

    def param_change(state):
        return common.to_floats(change_norms(state.params, key, bias))

    slots_per_step = flops_ssd_lm.slots_per_step(cfg, rows, seq)

    def flops_per_step(window):
        held = window["slots_held"] / window["slots_routed"] * slots_per_step if window.get("slots_routed") else 0.0
        per_row = rows / max(window["rows"], 1)
        return flops_ssd_lm.matmul_flops(cfg, rows * seq, held) + flops_ssd_lm.attention_flops(
            cfg, window["pairs"] * per_row)

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
