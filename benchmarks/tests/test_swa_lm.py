"""The ``swa_lm`` family at sizes a test run can hold: the job through
``child.run_job`` at the toy widths of ``data/swa_lm_toy.json`` (merged over the
cell's own files, as ``--rehearse`` merges ``rehearse.json``'s entries; the
kernels interpreted), the float8 control, a step that returns its state
unchanged, a program that forgets the window, the configuration held to the
published one, each new reader on a hand-made run, and ``flops_swa_lm`` against
a count by hand and against the masks written out."""

import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmarks import check, child, flops_swa_lm, run
from benchmarks.layer_metrics import _swa

CELL = "laguna-s-2-1.code8k"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def toy():
    with open(os.path.join(DATA, "swa_lm_toy.json")) as f:
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
    family = importlib.import_module("benchmarks.families.swa_lm")
    if broken == "no window":  # the program with its third rule replaced by plain causal
        from tensorflowonspark_tpu.models import decoder

        monkeypatch.setattr(decoder, "_rule", lambda labels, window=None: {})

    def build(spec, ctx, parts):
        job = family.build(spec, ctx, parts)
        if broken == "unchanged":
            real = job.step

            def unchanged(state, batch):
                _, metrics = real(jax.tree.map(jnp.copy, state), batch)
                return state, metrics

            job.step = _Callable(unchanged, real)
        return job

    return child.run_job(_spec(toy, scratch), _ctx(), 0.0, build=build, out=lambda line: None)


def test_sound_run_passes_and_counts(toy, tmp_path, monkeypatch):
    sound = _run(toy, tmp_path / "sound", None, monkeypatch)
    assert sound["check_ok"] and not sound["correct"], sound["check"]  # a rehearsal never reports correct
    window = sound["window"]
    assert window["steps"] >= 1 and window["compiles"] == 0
    spec = _spec(toy, tmp_path)
    record = dict(sound, workload=CELL, chips=1, config=spec["config"], traffic=spec["traffic"])
    # 4 of 16 experts held: 25 under even routing, which the calibration aims at on the first batch
    assert 21 < run.reader("per_layer", "swa_slots_held_pct")(record) < 29
    assert sound["parts"]["balance_s"] > 0
    assert run.reader("per_layer", "swa_pack_efficiency_pct")(record) > 50
    assert 0 < run.reader("per_layer", "swa_flash_win_blocks_needed_pct")(record) <= 100  # one block a toy row
    assert 0 < run.reader("per_layer", "swa_flash_win_steps_computing_pct")(record) <= 100
    assert 0 < run.reader("per_layer", "swa_flash_win_pairs_used_pct")(record) < 50  # a window of 48 in a block of 256
    traffic = spec["traffic"]
    tokens = traffic["batch_per_chip"] * traffic["seq_len"]
    assert window["units"] == window["steps"] * tokens
    routed_a_step = flops_swa_lm.slots_per_step(spec["config"], traffic["batch_per_chip"], traffic["seq_len"])
    assert routed_a_step == tokens * 4 * 4 and window["counters"]["moe_slots_routed_total"] % routed_a_step == 0
    assert 0 < window["counts"]["pairs_window"] < window["counts"]["pairs"]
    # what the last trace_steps batches held, kept apart from the window's sums for the kernels' rooflines
    assert sound["parts"]["traced_rows"] == traffic["trace_steps"] * traffic["batch_per_chip"]
    assert 0 < sound["parts"]["traced_pairs_window"] < sound["parts"]["traced_pairs"]
    # needed operations follow the slots that reached the held experts and the pairs of each rule
    bare = flops_swa_lm.matmul_flops(spec["config"], tokens, 0)
    assert bare < window["flops_per_step"] < 2 * flops_swa_lm.matmul_flops(spec["config"], tokens, routed_a_step)


@pytest.mark.parametrize("broken", ["unchanged", "no window"])
def test_a_broken_program_is_not_correct(toy, tmp_path, monkeypatch, broken):
    """A step that returns its state unchanged reads 1 everywhere; a program
    whose sliding layers attend the whole document fails by its gradient's
    direction (and more)."""
    result = _run(toy, tmp_path / "broken", broken, monkeypatch)
    assert not result["check_ok"]
    if broken == "unchanged":
        assert result["check"]["change_gap"] > 0.99 and result["check"]["grad_gap"] > 0.99
        assert result["check"]["dir_gap"] > 0.99
    else:
        assert result["check"]["dir_gap"] > 5 * toy["limits"]["dir_gap"]


def _batch(rows, seq, seed, vocab=500):
    """Two documents and a padded tail a row, as the text plane emits them (``seq + 1`` columns)."""
    rng = np.random.default_rng(seed)
    seg = np.repeat(np.array([[1] * 100 + [2] * 120 + [0] * (seq + 1 - 220)]), rows, axis=0).astype(np.int32)
    pos = np.repeat(np.concatenate([np.arange(100), np.arange(120), np.zeros(seq + 1 - 220, int)])[None], rows, axis=0)
    tokens = (rng.integers(3, vocab, (rows, seq + 1)) * (seg > 0)).astype(np.int32)
    return {"tokens": tokens, "segment_ids": seg, "positions": pos.astype(np.int32)}


def test_float8_control_is_not_correct(toy, tmp_path):
    import jax

    from benchmarks.reference import swa_lm as reference

    spec = _spec(toy, tmp_path)
    batch = _batch(2, spec["traffic"]["seq_len"], 5)
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
    # every leaf is heard, and the routers are frozen: their gradient is read, and they stay where they are
    assert set(want["first_grad"]) == set(map("/".join, reference.leaf_shapes(spec["config"])))
    assert set(want["param_change"]) == set(want["first_grad"]) == set(want["first_grad_sketch"])
    assert want["first_grad"]["layer_1/moe/router"] > 0 and want["param_change"]["layer_1/moe/router"] == 0
    assert want["param_change"]["layer_1/moe/experts_up"] > 0 and want["param_change"]["layer_3/attn/gate/kernel"] > 0
    assert want["param_change"]["layer_0/mlp/down/kernel"] > 0 and want["param_change"]["layer_2/moe/shared/up/kernel"] > 0


def test_model_config_is_the_published_one_with_the_share_named():
    _, cell, config, traffic = run.resolve(CELL)
    assert cell["chips"] == 1
    family = importlib.import_module("benchmarks.families.swa_lm")
    model = family.model_config(config, traffic["remat"])
    assert model["num_experts"] == 256 and model["experts_held"] == [0, 8] and model["remat"] is True
    published = config["source_config"]
    cut = set(config["reduced"])
    assert cut == {"num_hidden_layers", "num_experts", "vocab_size", "max_position_embeddings"}
    assert all(config[k] == v for k, v in published.items() if k not in cut)
    assert set(config["reduced_why"]) == cut and config["vocab_size"] * 8 == published["vocab_size"]
    from benchmarks.reference import swa_lm as reference
    from tensorflowonspark_tpu.models import decoder

    cfg = decoder.DecoderConfig.from_dict(model)
    assert cfg.plan == (("gqa", "swiglu", "add"),) + (("gqa", "moe", "add"),) * 4
    assert [cfg.heads_plan(i).heads for i in range(5)] == [48, 72, 72, 72, 48]
    assert [cfg.heads_plan(i).window for i in range(5)] == [None, 512, 512, 512, None]
    assert dict(cfg.heads_plan(0).rope)["partial_rotary_factor"] == 0.5 and dict(cfg.heads_plan(1).rope)["rope_theta"] == 10000
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads, cfg.intermediate_size) == (3072, 128, 8, 12288)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size, cfg.shared_width) == (256, 10, 1024, 1024)
    assert cfg.scoring_func == "softmax" and cfg.routed_scaling_factor == 2.5 and not cfg.qk_norm and cfg.gating == "per-head"
    assert reference.parameter_count(config) == config["parameters"]["here"] == 811017216
    assert traffic["seq_len"] == 8192 and traffic["batch_per_chip"] == 1 and traffic["corpus"]["seed"] == 38
    law = traffic["corpus"]["doc_tokens"]
    assert (law["median"], law["sigma"], law["min"], law["max"]) == (3000, 1.0, 128, 8192)


# ---- flops_swa_lm against a count by hand and against the masks ----------------------------------

HAND = {"hidden_size": 8, "num_key_value_heads": 2, "head_dim": 3, "intermediate_size": 20, "moe_intermediate_size": 6,
        "shared_expert_intermediate_size": 5, "router_experts": 10, "experts_held": [0, 2], "vocab_size": 32,
        "num_hidden_layers": 3, "num_experts_per_tok": 2, "sliding_window": 4,
        "layer_types": ["full_attention", "sliding_attention", "sliding_attention", "full_attention"],
        "num_attention_heads_per_layer": [4, 6, 6, 4], "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"]}


def test_flops_by_hand():
    assert flops_swa_lm.layer_kinds(HAND) == [(False, 4, True), (True, 6, False), (True, 6, False)]
    full = 2 * 8 * 12 + 2 * 8 * 6 + 8 * 4
    sliding = 2 * 8 * 18 + 2 * 8 * 6 + 8 * 6
    assert flops_swa_lm.macs_per_token(HAND) == 256 + (full + 3 * 8 * 20) + 2 * (sliding + 80 + 3 * 8 * 5)
    assert flops_swa_lm.expert_macs_per_slot(HAND) == 144 and flops_swa_lm.routed_layers(HAND) == 2
    assert flops_swa_lm.slots_per_step(HAND, 2, 16) == 2 * 16 * 2 * 2
    assert flops_swa_lm.matmul_flops(HAND, 10, 12) == 6 * (10 * flops_swa_lm.macs_per_token(HAND) + 144 * 12)
    # a pair: scores and values over 3, at the layer's heads; forward + twice that backward; one full, two sliding
    assert flops_swa_lm.attention_flops(HAND, 100, 30) == 6 * 2 * 3 * (4 * 100 + 2 * 6 * 30)
    assert flops_swa_lm.attention_flops(HAND, 100, 30, windowed=True) == 6 * 2 * 3 * 2 * 6 * 30
    assert flops_swa_lm.attention_flops(HAND, 100, 30, windowed=False) == 6 * 2 * 3 * 4 * 100
    assert flops_swa_lm.flash_bytes(HAND, 2, 16, True) == 2 * 16 * 2 * (4 * 6 * 3 + 4 * 2 * 3) * 2
    assert flops_swa_lm.flash_bytes(HAND, 2, 16, False) == 2 * 16 * (4 * 4 * 3 + 4 * 2 * 3) * 2
    assert flops_swa_lm.expert_flops(HAND, 7) == 6 * 144 * 7
    assert flops_swa_lm.expert_bytes(HAND, 7) == (2 * 2 * 3 * 48 * 3 + 2 * (16 + 12) * 7) * 2


def test_visible_pairs_are_the_masks():
    from benchmarks.reference import swa_lm as reference

    batch = _batch(2, 256, 3)
    batch["segment_ids"][1, 100:103] = 0  # a second row with other lengths: 100, 117, padding between
    seg = batch["segment_ids"][:, :-1]
    at = np.broadcast_to(np.arange(seg.shape[1])[None], seg.shape)
    for window in (None, 1, 48, 110, 4096):
        mask = np.asarray(reference.visible(seg, at, seg, at, window))
        assert flops_swa_lm.visible_pairs(seg, window) == int(mask.sum())
    assert flops_swa_lm.visible_pairs(seg, 48) < flops_swa_lm.visible_pairs(seg) == flops_swa_lm.visible_pairs(seg, 120)


def test_published_widths_need_what_the_issue_reckoned():
    _, _cell, config, _traffic = run.resolve(CELL)
    # forward products a token, in multiply-adds: the three sliding layers' projections the largest part
    sliding = 3 * (2 * 3072 * 72 * 128 + 2 * 3072 * 8 * 128 + 3072 * 72)
    assert sliding / flops_swa_lm.macs_per_token(config) == pytest.approx(0.40, abs=0.015)
    assert 3 * 3072 * 12288 / flops_swa_lm.macs_per_token(config) == pytest.approx(0.24, abs=0.015)
    # a step of 8192 tokens: about 23 TFLOP of needed products (31 with every layer recomputed)
    assert flops_swa_lm.matmul_flops(config, 8192, 4 * 2560) == pytest.approx(23.4e12, rel=2e-2)
    # a held expert's 320 slots a layer: under 1% of the products
    assert flops_swa_lm.expert_flops(config, 4 * 2560) / flops_swa_lm.matmul_flops(config, 8192, 4 * 2560) < 0.03
    assert flops_swa_lm.attention_flops(config, 1, 1) == 6 * 2 * 128 * (2 * 48 + 3 * 72)


# ---- the readers on hand-made runs ----------------------------------------------------------------

SWA = "jit(tos_train_step)/tos.loss_and_grad/jvp(Decoder)/layer_1/attn/tos.swa/dot_general"
SWA_BWD = "jit(tos_train_step)/tos.loss_and_grad/transpose(jvp(Decoder))/layer_1/attn/tos.swa/flash_bwd_dkv_win/pallas_call"
SWA_GATE = "jit(tos_train_step)/tos.loss_and_grad/jvp(Decoder)/layer_1/attn/tos.swa/tos.attn_gate/mul"
GQA = "jit(tos_train_step)/tos.loss_and_grad/jvp(Decoder)/layer_4/attn/tos.gqa/dot_general"
GQA_GATE = "jit(tos_train_step)/tos.loss_and_grad/jvp(Decoder)/layer_4/attn/tos.gqa/tos.attn_gate/logistic"
ROUTE = "jit(tos_train_step)/tos.loss_and_grad/jvp(Decoder)/layer_1/moe/tos.moe_route/gather"
EXPERTS = "ragged-dot-none"  # XLA's own name for the grouped product's kernel: no scope
SILU = "jit(tos_train_step)/tos.loss_and_grad/jvp(Decoder)/layer_1/moe/tos.moe_experts/mul"
OTHER = "jit(tos_train_step)/tos.optimizer/mul"


def _record(counters=None, gauges=None, ops=None):
    _, _cell, config, traffic = run.resolve(CELL)
    record = {
        "workload": CELL, "chips": 1, "config": config, "traffic": traffic, "peak": run._load("peaks.json")["TPU v5 lite"],
        "window": {"seconds": 10.0, "steps": 16, "compiles": 0, "counters": counters or {}, "gauges": gauges or {},
                   "spans": {"bench.next_batch": 0.05},
                   "counts": {"rows": 16, "real_tokens": 125000, "pairs": 16 * 20e6, "pairs_window": 16 * 3.6e6},
                   "flops_per_step": 28e12},
        "trace": None, "step_memory": {"total_bytes": 14.9e9},
        # the two traced rows: one of two short documents, fewer pairs than the window's mean row
        "parts": {"traced_rows": 2, "traced_pairs": 2 * 12e6, "traced_pairs_window": 2 * 3.0e6},
    }
    if ops is not None:
        record["trace"] = {"busy_s": 1.0, "window_s": 1.01, "steps": 2,
                           "kernel_s": {"flash_fwd_win": 0.06, "flash_bwd_dkv_win": 0.1, "flash_fwd_seg": 0.05,
                                        "flash_bwd_dkv_seg": 0.07, "flash_fwd_bd": 7.0}}
        record["_device_ops"] = ({"/device:TPU:0": ops}, (0.0, 1.01))
        record["_phase_shares"] = {"fwd": 25.0, "recompute": 20.0, "bwd": 50.0, "opt": 0.0, "other": 5.0}
    return record


OPS = [(SWA, 0.0, 0.2), (SWA_BWD, 0.2, 0.36), (SWA_GATE, 0.36, 0.38), (GQA, 0.38, 0.5), (GQA_GATE, 0.5, 0.51),
       (ROUTE, 0.51, 0.55), (EXPERTS, 0.55, 0.57), (SILU, 0.57, 0.58), (OTHER, 0.58, 1.0),
       (SWA, 2.0, 3.0)]  # the last lies outside the traced window
ROUTED = 16 * 8192 * 10 * 4.0
COUNTED = {"moe_slots_routed_total": ROUTED, "moe_slots_held_total": ROUTED / 32,
           "moe_layers_compact_total": 63.0, "moe_layers_at_bound_total": 1.0,
           "train_step_dispatch_seconds_total": 0.048, "train_steps_dispatched_total": 16.0,
           "train_step_stall_seconds_total": 0.0, "flash_blocks_needed_total": 1500.0, "flash_blocks_dense_total": 2176.0,
           "flash_win_blocks_needed_total": 400.0, "flash_win_blocks_dense_total": 2176.0, "flash_win_grid_steps_total": 400.0,
           "flash_win_pairs_visible_total": 55e6, "flash_win_pairs_in_blocks_total": 400.0 * 512 * 512}


@pytest.mark.parametrize("name,value", [
    ("swa_compiles_in_window", 0), ("swa_input_wait_pct", 0.5), ("swa_pack_efficiency_pct", 125000 / (16 * 8192) * 100),
    ("swa_step_device_ms", 500.0), ("swa_mfu_pct", 100 * 28e12 * 16 / (10 * 197e12)),
    ("swa_device_idle_pct", 100 * (1 - 1 / 1.01)), ("swa_step_hbm_gb", 14.9), ("swa_dispatch_ms_per_step", 3.0),
    ("swa_step_stall_pct", 0.0), ("swa_fwd_pct", 25.0), ("swa_recompute_pct", 20.0), ("swa_bwd_pct", 50.0),
    ("swa_window_attn_time_pct", 38.0), ("swa_full_attn_time_pct", 13.0), ("swa_gate_time_pct", 3.0),
    ("swa_experts_time_pct", 3.0), ("swa_route_time_pct", 4.0), ("swa_slots_held_pct", 3.125),
    ("swa_layers_compact_pct", 100 * 63 / 64), ("swa_flash_win_time_pct", 16.0), ("swa_flash_full_time_pct", 12.0),
    ("swa_flash_win_blocks_needed_pct", 100 * 400 / 2176), ("swa_flash_win_steps_computing_pct", 100.0),
    ("swa_flash_win_pairs_used_pct", 100 * 55e6 / (400 * 512 * 512)),
])
def test_reader_on_a_hand_made_run(name, value):
    record = _record(COUNTED, {}, OPS)
    assert run.reader("per_layer", name)(record) == pytest.approx(value, rel=1e-6, abs=1e-9)


def test_rooflines_on_a_hand_made_run():
    record = _record(COUNTED, {}, OPS)
    cfg, peak = record["config"], record["peak"]
    # the sliding layers: the traced rows' 3.0e6 visible pairs a row and layer at 72 heads (not the window's mean
    # row's 3.6e6); their two kernels 0.16 s over the two traced steps
    least = max(flops_swa_lm.attention_flops(cfg, 12e6, 3.0e6, windowed=True) / peak["bf16_flops_per_s"],
                flops_swa_lm.flash_bytes(cfg, 1, 8192, True) / peak["hbm_bytes_per_s"])
    got = run.reader("per_layer", "swa_flash_win_roofline_pct")(record)
    assert got == pytest.approx(100 * least / 0.08) and got < 100
    # the full layers: the traced rows' 12e6 pairs at 48 heads, two layers; the causal kernels 0.12 s over two steps
    least = max(flops_swa_lm.attention_flops(cfg, 12e6, 3.0e6, windowed=False) / peak["bf16_flops_per_s"],
                flops_swa_lm.flash_bytes(cfg, 1, 8192, False) / peak["hbm_bytes_per_s"])
    got = run.reader("per_layer", "swa_flash_full_roofline_pct")(record)
    assert got == pytest.approx(100 * least / 0.06) and got < 100
    # a run whose family kept no traced rows' pairs reads nothing
    record["parts"] = {}
    assert run.reader("per_layer", "swa_flash_full_roofline_pct")(record) is None
    record = _record(COUNTED, {}, OPS)
    # experts: 1/32 of a step's 8192 x 10 x 4 slots; the grouped product's kernels 0.02 s over two steps
    assert _swa.slots_held_per_step(record) == pytest.approx(8192 * 10 * 4 / 32)
    least = max(flops_swa_lm.expert_flops(cfg, 10240) / peak["bf16_flops_per_s"],
                flops_swa_lm.expert_bytes(cfg, 10240) / peak["hbm_bytes_per_s"])
    got = run.reader("per_layer", "swa_experts_roofline_pct")(record)
    assert got == pytest.approx(100 * least / 0.01) and got < 100


@pytest.mark.parametrize("name", [
    "swa_window_attn_time_pct", "swa_full_attn_time_pct", "swa_gate_time_pct", "swa_experts_time_pct", "swa_route_time_pct",
    "swa_flash_win_roofline_pct", "swa_flash_full_roofline_pct", "swa_experts_roofline_pct", "swa_slots_held_pct",
    "swa_layers_compact_pct", "swa_dispatch_ms_per_step", "swa_fwd_pct", "swa_step_device_ms", "swa_device_idle_pct",
    "swa_flash_win_time_pct", "swa_flash_full_time_pct", "swa_flash_win_blocks_needed_pct",
    "swa_flash_win_steps_computing_pct", "swa_flash_win_pairs_used_pct",
])
def test_reader_finds_nothing_in_a_program_without_the_scopes_and_counters(name):
    """A program without this PR's kernels, scopes and counters: no counter, no trace (untraced run) or a
    trace whose operations carry no such scope and whose kernels are another rule's."""
    assert run.reader("per_layer", name)(_record()) is None
    unscoped = _record({}, {}, [("jit(tos_train_step)/tos.loss_and_grad/jvp()/dot_general", 0.0, 0.5)])
    unscoped["trace"]["kernel_s"] = {"flash_fwd_bd": 0.1}
    unscoped["window"]["counts"].pop("pairs_window")
    unscoped["_phase_shares"] = None
    if name not in ("swa_step_device_ms", "swa_device_idle_pct"):
        assert run.reader("per_layer", name)(unscoped) is None
