"""The ``bd_lm`` family at sizes a test run can hold: the job through
``child.run_job`` at the toy widths of ``data/bd_lm_toy.json`` (merged over the
cell's own files, as ``--rehearse`` merges ``rehearse.json``'s entries; the
kernels interpreted), the float8 control, a step that returns its state
unchanged, the routers' calibration, each new reader on a hand-made run, and
``flops_bd_lm`` against a count by hand and against the mask written out."""

import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmarks import check, child, flops_bd_lm, run
from benchmarks.layer_metrics import _bd

CELL = "sdar-30b-a3b.bd4-packed4k"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def toy():
    with open(os.path.join(DATA, "bd_lm_toy.json")) as f:
        return json.load(f)


def _spec(toy, scratch, seconds=0.5):
    _, _cell, config, traffic = run.resolve(CELL)
    return {
        "workload": CELL, "chips": 1, "seed": 2147483659, "seconds": seconds, "trace": False, "rehearse": True,
        "config": run._merge(config, toy["config"]), "traffic": run._merge(traffic, toy["traffic"]),
        "scratch": str(scratch),
    }


def _ctx():
    return types.SimpleNamespace(initialize_distributed=lambda: None, num_processes=1, num_workers=1, executor_id=0)


class _Callable:
    def __init__(self, fn, real):
        self.fn, self.lower, self.drain = fn, real.lower, real.drain

    def __call__(self, *args):
        return self.fn(*args)


def _run(toy, scratch, broken, monkeypatch):
    import jax
    import jax.numpy as jnp

    os.makedirs(scratch)
    monkeypatch.setattr(check, "load_limits", lambda workload: toy["limits"])
    family = importlib.import_module("benchmarks.families.bd_lm")

    def build(spec, ctx, parts):
        job = family.build(spec, ctx, parts)
        if broken:
            real = job.step

            def unchanged(state, batch):
                _, metrics = real(jax.tree.map(jnp.copy, state), batch)
                return state, metrics

            job.step = _Callable(unchanged, real)
        return job

    return child.run_job(_spec(toy, scratch), _ctx(), 0.0, build=build, out=lambda line: None)


def test_sound_run_passes_and_counts(toy, tmp_path, monkeypatch):
    sound = _run(toy, tmp_path / "sound", False, monkeypatch)
    assert sound["check_ok"] and not sound["correct"], sound["check"]  # a rehearsal never reports correct
    window = sound["window"]
    assert window["steps"] >= 1 and window["compiles"] == 0
    spec = _spec(toy, tmp_path)
    record = dict(sound, workload=CELL, chips=1, config=spec["config"], traffic=spec["traffic"])
    # 4 of 16 experts held: 25 under even routing, which the calibration aims at on the first batch
    assert 22 < run.reader("per_layer", "bd_slots_held_pct")(record) < 28
    assert sound["parts"]["balance_s"] > 0
    assert run.reader("per_layer", "bd_expert_load_max_over_mean")(record) >= 1.0
    assert run.reader("per_layer", "bd_pack_efficiency_pct")(record) > 50
    assert 35 < run.reader("per_layer", "bd_masked_tokens_pct")(record) < 65
    assert run.reader("per_layer", "bd_noise_pct")(record) > 0
    assert 0 < run.reader("per_layer", "bd_flash_blocks_needed_pct")(record) <= 100  # one block a toy row
    assert 0 < run.reader("per_layer", "bd_flash_steps_computing_pct")(record) <= 100
    # the unit is a data token; the routed layers count both halves of every row
    traffic = spec["traffic"]
    tokens = traffic["batch_per_chip"] * traffic["seq_len"]
    assert window["units"] == window["steps"] * tokens
    routed_a_step = flops_bd_lm.slots_per_step(spec["config"], traffic["batch_per_chip"], traffic["seq_len"])
    assert window["counters"]["moe_slots_routed_total"] % routed_a_step == 0
    # needed operations follow the slots that reached the held experts
    bare = flops_bd_lm.matmul_flops(spec["config"], tokens, 0)
    assert bare < window["flops_per_step"] < 2 * flops_bd_lm.matmul_flops(spec["config"], tokens, routed_a_step)


def test_step_that_returns_its_state_unchanged_reads_one(toy, tmp_path, monkeypatch):
    broken = _run(toy, tmp_path / "broken", True, monkeypatch)
    assert not broken["check_ok"]
    assert broken["check"]["change_gap"] > 0.99 and broken["check"]["grad_gap"] > 0.99
    assert broken["check"]["dir_gap"] > 0.99


def _batch(cfg, rows, seq, seed):
    """Two documents and a padded tail a row, noised by the program's own stage."""
    from tensorflowonspark_tpu.data.text_plane import noise_blocks

    rng = np.random.default_rng(seed)
    seg = np.repeat(np.array([[1] * 100 + [2] * 120 + [0] * (seq - 220)]), rows, axis=0).astype(np.int32)
    pos = np.repeat(np.concatenate([np.arange(100), np.arange(120), np.zeros(seq - 220, int)])[None], rows, axis=0)
    tokens = (rng.integers(3, cfg["mask_token_id"], (rows, seq)) * (seg > 0)).astype(np.int32)
    noised, weights = noise_blocks(tokens, seg, pos, cfg["block_length"], cfg["mask_token_id"], 1e-3, rng)
    return {"tokens": tokens, "noised_tokens": noised, "loss_weights": weights, "segment_ids": seg,
            "positions": pos.astype(np.int32)}


def test_float8_control_is_not_correct(toy, tmp_path):
    import jax

    from benchmarks.reference import bd_lm as reference

    spec = _spec(toy, tmp_path)
    batch = _batch(spec["config"], 2, spec["traffic"]["seq_len"], 5)
    key, devices = jax.random.PRNGKey(7), jax.devices()[:1]
    want = reference.follow(spec["config"], key, [batch] * 2, devices)
    control = reference.follow(spec["config"], key, [batch] * 2, devices, quant="fp8")
    read = check.readings(control, want)
    for limits in (toy["limits"], check.load_limits(CELL)):
        correct, _ = check.judge(read, limits)
        assert not correct, read
        assert read["dir_gap"] > limits["dir_gap"], read
        same, _ = check.judge(check.readings(want, want), limits)
        assert same
    # every leaf is heard (no hyper-connection maps, no selection bias here), and the routers are frozen:
    # their gradient is read, and they stay where they are
    assert set(want["first_grad"]) == set(map("/".join, reference.leaf_shapes(spec["config"])))
    assert set(want["param_change"]) == set(want["first_grad"]) == set(want["first_grad_sketch"])
    assert want["first_grad"]["layer_1/moe/router"] > 0 and want["param_change"]["layer_1/moe/router"] == 0
    assert want["param_change"]["layer_1/moe/experts_up"] > 0 and want["param_change"]["layer_0/attn/k_norm/scale"] > 0


def test_calibrated_routers_give_the_held_experts_their_share_and_follow_the_seed(toy, tmp_path):
    import jax

    from benchmarks.reference import bd_lm as reference

    cfg = _spec(toy, tmp_path)["config"]
    batch = _batch(cfg, 2, 256, 6)
    first, count = cfg["experts_held"]

    def held_share(key, routers):
        """The held experts' share of the slots, per layer, on ``batch``."""
        params = reference.init_params(key, cfg, routers)
        tokens, positions, ids, block, noised = reference.doubled(batch, cfg)
        x, shares = params["embed"]["embedding"][tokens], []

        def routed(h, p, cfg, quant):
            weights = reference.routing(h.reshape(-1, h.shape[-1]), p["router"], cfg, quant)
            shares.append(float((weights[:, first:first + count] > 0).sum() / (weights > 0).sum()))
            return reference.experts(h, p, cfg, quant)

        with jax.default_matmul_precision("highest"):
            for i in range(cfg["num_hidden_layers"]):
                x = reference.layer_forward(x, params["layer_{}".format(i)], positions, ids, block, noised, cfg, routed=routed)
        return np.array(shares)

    even, found = count / cfg["router_experts"], {}
    for seed in (7, 8):
        key = jax.random.PRNGKey(seed)
        found[seed] = routers = jax.device_get(reference.calibrated_routers(key, cfg, batch))
        assert sorted(routers) == ["layer_0", "layer_1"] and routers["layer_0"].shape == (cfg["hidden_size"], cfg["router_experts"])
        # the sum over the layers is brought to the even share; a seeded router's is the seed's luck
        assert abs(held_share(key, routers).mean() - even) < 0.005
        # only the held experts' columns moved, each by the same vector
        moved = routers["layer_0"] - np.asarray(reference.init_params(key, cfg)["layer_0"]["moe"]["router"])
        assert np.abs(moved[:, first + count:]).max() == 0 and np.abs(moved[:, :first]).max() == 0
        assert np.allclose(moved[:, first], moved[:, first + count - 1])
    assert max(abs(held_share(jax.random.PRNGKey(s), None).mean() - even) for s in (7, 8, 9, 10)) > 0.01
    assert not np.allclose(found[7]["layer_0"], found[8]["layer_0"])


def test_model_config_is_the_published_one_with_the_share_named():
    _, _cell, config, traffic = run.resolve(CELL)
    family = importlib.import_module("benchmarks.families.bd_lm")
    model = family.model_config(config, traffic["remat"])
    assert model["num_experts"] == 128 and model["experts_held"] == [0, 16] and model["remat"] is True
    published = config["source_config"]
    cut = set(config["reduced"])
    assert cut == {"num_hidden_layers", "num_experts", "vocab_size", "max_position_embeddings"}
    assert all(config[k] == v for k, v in published.items() if k not in cut)
    from benchmarks.reference import bd_lm as reference
    from tensorflowonspark_tpu.models import decoder

    cfg = decoder.DecoderConfig.from_dict(model)
    assert cfg.plan == (("gqa", "moe", "add"),) * 6 and cfg.scoring_func == "softmax" and cfg.n_routed_experts == 128
    assert cfg.objective == "block_diffusion" and cfg.block_length == 4 and cfg.mask_id == config["vocab_size"] - 1
    assert reference.parameter_count(config) == config["parameters"]["here"] == 645623296
    assert family.noising(config) == {"block_length": 4, "mask_id": 18991, "t_min": 0.001}
    assert traffic["seq_len"] == 4096 and traffic["batch_per_chip"] in (1, 2) and traffic["corpus"]["seed"] == 33
    law = traffic["corpus"]["doc_tokens"]
    assert (law["median"], law["sigma"], law["min"], law["max"]) == (1400, 1.0, 64, 4096)


# ---- flops_bd_lm against a count by hand and against the mask ------------------------------------

HAND = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 3, "moe_intermediate_size": 6,
        "router_experts": 10, "experts_held": [0, 2], "vocab_size": 32, "num_hidden_layers": 3, "num_experts_per_tok": 2,
        "block_length": 4}


def test_flops_by_hand():
    m = flops_bd_lm.macs_per_position(HAND)
    assert m == {"q_and_o": 2 * 8 * 12, "k_and_v": 2 * 8 * 6, "router": 80, "head": 256}
    assert flops_bd_lm.expert_macs_per_slot(HAND) == 144
    assert flops_bd_lm.slots_per_step(HAND, 2, 16) == 2 * 32 * 2 * 3
    # 10 data tokens: 5 half-layers of everything, the last layer's clean half by k and v, the head once
    per_position = 192 + 96 + 80
    assert flops_bd_lm.matmul_flops(HAND, 10, 12) == 6 * (10 * (5 * per_position + 96 + 256) + 144 * 12 * 5 / 6)
    # a pair: scores and values over 3, four heads; forward + twice that backward; two and a half layers
    assert flops_bd_lm.attention_flops(HAND, 100) == 2.5 * 6 * 2 * 3 * 4 * 100
    assert flops_bd_lm.flash_bytes(HAND, 2, 16) == 2 * 16 * (4 * 4 * 3 * 5 + 4 * 2 * 3 * 6) * 2
    assert flops_bd_lm.expert_flops(HAND, 7) == 6 * 144 * 7
    assert flops_bd_lm.expert_bytes(HAND, 7) == (3 * 2 * 3 * 48 * 3 + 2 * (16 + 12) * 7) * 2


def test_visible_pairs_are_the_masks():
    from benchmarks.reference import bd_lm as reference

    batch = _batch({"mask_token_id": 500, "block_length": 4}, 2, 256, 3)
    batch["segment_ids"][1, 100:103] = 0  # a second row with other lengths: 100, 117 (a tail of 1), padding between
    batch["positions"][1, 103:220] = np.arange(117)
    _tokens, _positions, ids, block, noised = reference.doubled(batch, {"block_length": 4})
    mask = np.asarray(reference.visible((ids, block, noised), (ids, block, noised)))
    assert flops_bd_lm.visible_pairs(batch["segment_ids"], batch["positions"], 4) == int(mask.sum())
    half = batch["tokens"].shape[1]
    assert int(mask[:, half:].sum()) * 2 == int(mask.sum())  # the noised queries see half the pairs


def test_published_widths_need_what_the_issue_reckoned():
    _, _cell, config, _traffic = run.resolve(CELL)
    m = flops_bd_lm.macs_per_position(config)
    assert m["q_and_o"] + m["k_and_v"] == pytest.approx(18.87e6, rel=1e-3) and m["router"] == 262144
    assert flops_bd_lm.expert_macs_per_slot(config) == pytest.approx(4.72e6, rel=1e-3)
    # a row of 4096 data tokens: about 0.39 TFLOP of products forward a layer with one held expert a position
    forward = 2 * 8192 * (m["q_and_o"] + m["k_and_v"] + m["router"] + flops_bd_lm.expert_macs_per_slot(config))
    assert forward == pytest.approx(0.39e12, rel=2e-2)
    assert flops_bd_lm.attention_flops(config, 1) == 5.5 * 6 * 2 * 128 * 32


# ---- the readers on hand-made runs ----------------------------------------------------------------

GQA = "jit(tos_train_step)/tos.loss_and_grad/jvp(Decoder)/layer_1/attn/tos.gqa/dot_general"
GQA_BWD = "jit(tos_train_step)/tos.loss_and_grad/transpose(jvp(Decoder))/layer_1/attn/tos.gqa/flash_bwd_dkv_bd/pallas_call"
ROUTE = "jit(tos_train_step)/tos.loss_and_grad/jvp(Decoder)/layer_1/moe/tos.moe_route/gather"
EXPERTS = "ragged-dot-none"  # XLA's own name for the grouped product's kernel: no scope
SILU = "jit(tos_train_step)/tos.loss_and_grad/jvp(Decoder)/layer_1/moe/tos.moe_experts/mul"
OTHER = "jit(tos_train_step)/tos.optimizer/mul"


def _record(counters=None, gauges=None, ops=None):
    _, _cell, config, traffic = run.resolve(CELL)
    traffic = dict(traffic, batch_per_chip=2)
    record = {
        "workload": CELL, "chips": 1, "config": config, "traffic": traffic, "peak": run._load("peaks.json")["TPU v5 lite"],
        "window": {"seconds": 10.0, "steps": 8, "compiles": 0, "counters": counters or {}, "gauges": gauges or {},
                   "spans": {"bench.next_batch": 0.05}, "counts": {"rows": 16, "real_tokens": 64000, "pairs": 16 * 9.8e6},
                   "flops_per_step": 40e12},
        "trace": None, "step_memory": {"total_bytes": 13.1e9},
    }
    if ops is not None:
        record["trace"] = {"busy_s": 1.0, "window_s": 1.01, "steps": 2,
                           "kernel_s": {"flash_fwd_bd": 0.1, "flash_bwd_dkv_bd": 0.2, "flash_fwd_seg": 7.0}}
        record["_device_ops"] = ({"/device:TPU:0": ops}, (0.0, 1.01))
        record["_phase_shares"] = {"fwd": 25.0, "recompute": 20.0, "bwd": 50.0, "opt": 0.0, "other": 5.0}
    return record


OPS = [(GQA, 0.0, 0.2), (GQA_BWD, 0.2, 0.4), (ROUTE, 0.4, 0.55), (EXPERTS, 0.55, 0.63), (SILU, 0.63, 0.65),
       (OTHER, 0.65, 1.0), (GQA, 2.0, 3.0)]  # the last lies outside the traced window
ROUTED = 8 * 2 * 8192 * 8 * 6.0
COUNTED = {"moe_slots_routed_total": ROUTED, "moe_slots_held_total": ROUTED / 8,
           "train_step_dispatch_seconds_total": 0.024, "train_steps_dispatched_total": 8.0,
           "train_step_stall_seconds_total": 0.0, "flash_blocks_needed_total": 410.0, "flash_blocks_dense_total": 1000.0,
           "flash_grid_steps_total": 500.0, "h2d_place_seconds_total": 0.02, "data_consumer_wait_seconds_total": 0.03,
           "data_producer_noise_seconds_total": 0.04, "bd_positions_masked_total": 31000.0, "bd_tokens_real_total": 64000.0}


@pytest.mark.parametrize("name,value", [
    ("bd_compiles_in_window", 0), ("bd_input_wait_pct", 0.5), ("bd_pack_efficiency_pct", 64000 / (16 * 4096) * 100),
    ("bd_step_device_ms", 500.0), ("bd_mfu_pct", 100 * 40e12 * 8 / (10 * 197e12)),
    ("bd_device_idle_pct", 100 * (1 - 1 / 1.01)), ("bd_step_hbm_gb", 13.1), ("bd_dispatch_ms_per_step", 3.0),
    ("bd_step_stall_pct", 0.0), ("bd_fwd_pct", 25.0), ("bd_recompute_pct", 20.0), ("bd_bwd_pct", 50.0),
    ("bd_attn_time_pct", 40.0), ("bd_experts_time_pct", 10.0), ("bd_route_time_pct", 15.0),
    ("bd_slots_held_pct", 12.5), ("bd_expert_load_max_over_mean", 1.25),
    ("bd_flash_time_pct", 30.0), ("bd_flash_blocks_needed_pct", 41.0), ("bd_flash_steps_computing_pct", 82.0),
    ("bd_h2d_place_pct", 0.2), ("bd_batch_wait_pct", 0.3), ("bd_noise_pct", 0.4), ("bd_masked_tokens_pct", 3100 / 64),
])
def test_reader_on_a_hand_made_run(name, value):
    record = _record(COUNTED, {"moe_expert_load_max_over_mean": 1.25}, OPS)
    assert run.reader("per_layer", name)(record) == pytest.approx(value, rel=1e-6, abs=1e-9)


def test_rooflines_on_a_hand_made_run():
    record = _record(COUNTED, {}, OPS)
    cfg, peak = record["config"], record["peak"]
    # attention: 9.8e6 visible pairs a row, two rows a step; the two mask kernels (not the causal one) 0.3 s over two steps
    least = max(flops_bd_lm.attention_flops(cfg, 2 * 9.8e6) / peak["bf16_flops_per_s"],
                flops_bd_lm.flash_bytes(cfg, 2, 4096) / peak["hbm_bytes_per_s"])
    got = run.reader("per_layer", "bd_flash_roofline_pct")(record)
    assert got == pytest.approx(100 * least / 0.15) and got < 100
    # experts: an eighth of a step's 2 x 8192 x 8 x 6 slots; the grouped product's kernels 0.08 s over two steps
    assert _bd.slots_held_per_step(record) == pytest.approx(2 * 8192 * 8 * 6 / 8)
    least = max(flops_bd_lm.expert_flops(cfg, 98304) / peak["bf16_flops_per_s"],
                flops_bd_lm.expert_bytes(cfg, 98304) / peak["hbm_bytes_per_s"])
    got = run.reader("per_layer", "bd_experts_roofline_pct")(record)
    assert got == pytest.approx(100 * least / 0.04) and got < 100


@pytest.mark.parametrize("name", [
    "bd_attn_time_pct", "bd_experts_time_pct", "bd_route_time_pct", "bd_flash_roofline_pct", "bd_experts_roofline_pct",
    "bd_slots_held_pct", "bd_expert_load_max_over_mean", "bd_dispatch_ms_per_step", "bd_fwd_pct", "bd_step_device_ms",
    "bd_device_idle_pct", "bd_flash_time_pct", "bd_flash_blocks_needed_pct", "bd_flash_steps_computing_pct",
    "bd_h2d_place_pct", "bd_batch_wait_pct", "bd_noise_pct", "bd_masked_tokens_pct",
])
def test_reader_finds_nothing_in_a_program_without_the_scopes_and_counters(name):
    """A program without this PR's kernels, scope, span and counters: no counter, no gauge, no trace (untraced
    run) or a trace whose operations carry no such scope and whose kernels are the causal ones."""
    assert run.reader("per_layer", name)(_record()) is None
    unscoped = _record({}, {}, [("jit(tos_train_step)/tos.loss_and_grad/jvp()/dot_general", 0.0, 0.5)])
    unscoped["trace"]["kernel_s"] = {"flash_fwd_seg": 0.1}
    unscoped["_phase_shares"] = None
    if name not in ("bd_step_device_ms", "bd_device_idle_pct"):
        assert run.reader("per_layer", name)(unscoped) is None
