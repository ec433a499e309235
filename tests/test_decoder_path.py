"""The plan-built decoder on the normal path: the flash kernels at unequal
widths, ``SyncDataParallel.compile_train_step`` booking what the routed
layers counted, the example's ``--model`` / ``--model_config``, the layer
kinds' placement rules, and the published configuration's parameter count.
(The layer kinds and the whole model against the plain reference:
``tests/test_decoder_model.py``.)"""

import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_testutil import ROOT, close, packed_batch, params, program_loss, reference  # noqa: F401  (params is a fixture)
from tensorflowonspark_tpu import obs
from tensorflowonspark_tpu.models import decoder
from tensorflowonspark_tpu.ops.flash_attention import flash_attention
from tensorflowonspark_tpu.parallel.ring_attention import plain_attention
from tensorflowonspark_tpu.train import SyncDataParallel

# ---- the flash kernels at unequal widths ----------------------------------------------------------


@pytest.mark.parametrize("segmented", [True, False], ids=["segments", "causal"])
def test_flash_kernels_take_values_narrower_than_keys(segmented):
    """q/k 24 wide against v 16 (192/128 in the small): outputs and dq, dk, dv
    against plain attention, over several blocks."""
    keys = jax.random.split(jax.random.PRNGKey(16), 4)
    q, k = (jax.random.normal(key, (2, 2, 256, 24), jnp.float32) for key in keys[:2])
    v = jax.random.normal(keys[2], (2, 2, 256, 16), jnp.float32)
    seg = None
    if segmented:
        ids = np.zeros((2, 256), np.int32)
        ids[0, :100], ids[0, 100:180], ids[0, 180:230] = 1, 2, 3
        ids[1, :256] = 1
        seg = jnp.asarray(ids)
    scale = 0.37

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=scale, segment_ids=seg, block_q=64, block_k=64,
                               interpret=True)

    def plain(q, k, v):
        return plain_attention(q, k, v, causal=True, scale=scale, segment_ids=seg)

    real = jnp.ones((2, 1, 256, 1)) if seg is None else (seg > 0)[:, None, :, None]
    out = flash(q, k, v)
    assert out.shape == (2, 2, 256, 16)
    close(out * real, plain(q, k, v) * real, 1e-4)
    weigh = jax.random.normal(keys[3], out.shape) * real
    grads = jax.grad(lambda *a: jnp.sum(flash(*a) * weigh), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * weigh), argnums=(0, 1, 2))(q, k, v)
    assert [g.shape[-1] for g in grads] == [24, 24, 16]
    for g, w in zip(grads, want):
        close(g, w, 2e-4)


# ---- the normal path ------------------------------------------------------------------------------


def test_train_step_books_what_the_routed_layers_counted(params):
    """Through ``SyncDataParallel.compile_train_step``: the step's metrics carry
    the counts out and ``TrainStep`` books them once the step has finished."""
    import optax

    model, loss_fn = program_loss()
    strategy = SyncDataParallel(mesh=None)
    optimizer = optax.adamw(1e-3)
    state = strategy.create_state(lambda: {"params": params}, optimizer)
    step = strategy.compile_train_step(loss_fn, optimizer, has_aux=True)
    before = obs.snapshot()["counters"]
    routed0 = before.get("moe_slots_routed_total", {}).get("value", 0)
    held0 = before.get("moe_slots_held_total", {}).get("value", 0)
    losses = []
    for i in range(3):
        state, metrics = step(state, strategy.shard_batch(
            {k: np.asarray(v) for k, v in packed_batch(rows=8, seed=i).items()}))
        jax.block_until_ready(metrics["loss"])
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    after = obs.snapshot()
    # the last step is booked when the next call (or none) finds it finished
    routed = after["counters"]["moe_slots_routed_total"]["value"] - routed0
    held = after["counters"]["moe_slots_held_total"]["value"] - held0
    assert routed in (2 * 1536, 3 * 1536) and 0 < held < routed
    assert after["gauges"]["moe_expert_load_max_over_mean"]["value"] >= 1.0
    step.drain()  # what the loop's end books: the steps no later call looked at
    assert obs.snapshot()["counters"]["moe_slots_routed_total"]["value"] - routed0 == 3 * 1536


def test_example_builds_and_steps_from_a_model_config(tmp_path, capsys):
    """``transformer_spark.py --model decoder --model_config <file>``: the
    example's ``main_fun`` (what ``TFCluster.run`` runs in the jax child)
    builds the registered model from the file and trains it through the text
    pipeline, the strategy and the compiled step."""
    sys.path.insert(0, os.path.join(ROOT, "examples", "transformer"))
    import transformer_spark as example

    with open(os.path.join(ROOT, "examples", "transformer", "decoder_toy.json")) as f:
        model_cfg = json.load(f)
    data_dir = str(tmp_path / "corpus")
    example.make_text_corpus(data_dir, num_shards=2, records_per_shard=64)
    args = example.build_parser().parse_args([
        "--model", "decoder", "--model_config", "decoder_toy.json", "--data_dir", data_dir, "--seq_len", "64",
        "--batch_size", "8", "--train_steps", "3", "--log_steps", "1", "--tokenizer", "word", "--dtype", "float32",
        "--attention", "plain",
    ])
    args.model_cfg = model_cfg
    ctx = types.SimpleNamespace(
        initialize_distributed=lambda: None, num_processes=1, num_workers=1, executor_id=0, distributed=False)
    example.main_fun(args, ctx)
    out = capsys.readouterr().out
    assert "step 3: loss" in out and "transformer training complete" in out
    losses = [float(line.split("loss ")[1].split()[0]) for line in out.splitlines() if line.startswith("step ")]
    assert losses[-1] < losses[0]


def test_param_rules_place_the_kinds_on_a_tp_mesh(params):
    from jax.sharding import Mesh, PartitionSpec as P

    model, _ = program_loss()
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    specs = decoder.make_param_specs(model)(params, mesh)
    assert specs["layer_1"]["attn"]["q_b"]["kernel"] == P(None, "tp", None)
    assert specs["layer_1"]["attn"]["o"]["kernel"] == P("tp", None, None)
    assert specs["layer_0"]["mlp"]["down"]["kernel"] == P("tp", None)
    assert specs["layer_1"]["moe"]["experts_gate"] == P(None, None, "tp")
    assert specs["layer_1"]["moe"]["shared"]["up"]["kernel"] == P(None, "tp")
    assert specs["layer_1"]["moe"]["router"] == P(None, None)
    assert specs["layer_1"]["res_attn"]["phi_res"] == P(None, None, None)
    assert specs["lm_head"]["kernel"] == P(None, "tp")


def test_published_configuration_counts_its_parameters():
    """``benchmarks/configs/xing4-a4b.json``: one chip's share of the eight
    that divide each layer is 759.3M parameters of the model's 29.5B."""
    with open(os.path.join(ROOT, "benchmarks", "configs", "xing4-a4b.json")) as f:
        cfg = json.load(f)
    assert reference.parameter_count(cfg) == pytest.approx(759.3e6, rel=1e-3)
    source = cfg["source_config"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "num_attention_heads", "num_experts_per_tok",
                "hc_mult", "rope_scaling"):
        assert cfg[key] == source[key], key  # no width is cut
    assert cfg["router_experts"] == source["n_routed_experts"] == 64
    changed = {k for k, v in source.items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == set(cfg["reduced_why"])
    whole = dict(cfg, num_hidden_layers=40, first_k_dense_replace=2, vocab_size=131072, experts_held=[0, 64])
    assert reference.parameter_count(whole) == pytest.approx(29.5e9, rel=5e-3)
