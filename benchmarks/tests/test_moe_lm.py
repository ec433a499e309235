"""The ``moe_lm`` family at sizes a test run can hold: the job through
``child.run_job`` at the toy widths of ``data/moe_lm_toy.json`` (merged over
the cell's own files, as ``--rehearse`` merges ``rehearse.json``'s entries),
the float8 control, a step that returns its state unchanged, each new reader
on a hand-made run, and ``flops_moe_lm`` against a count by hand."""

import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmarks import check, child, flops_moe_lm, run
from benchmarks.layer_metrics import _moe

CELL = "xing4-a4b.packed8k"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def toy():
    with open(os.path.join(DATA, "moe_lm_toy.json")) as f:
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
        self.fn, self.lower = fn, real.lower

    def __call__(self, *args):
        return self.fn(*args)


def _run(toy, scratch, broken, monkeypatch):
    import jax
    import jax.numpy as jnp

    os.makedirs(scratch)
    monkeypatch.setattr(check, "load_limits", lambda workload: toy["limits"])
    family = importlib.import_module("benchmarks.families.moe_lm")

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
    assert window["counters"]["moe_slots_routed_total"] > 0
    record = dict(sound, workload=CELL, chips=1, config=_spec(toy, tmp_path)["config"],
                  traffic=_spec(toy, tmp_path)["traffic"])
    held_pct = run.reader("per_layer", "moe_slots_held_pct")(record)
    assert 30 < held_pct < 45  # 3 of 8 experts held: 37.5 under even routing, and the bias starts balanced
    assert sound["parts"]["balance_s"] > 0
    assert run.reader("per_layer", "moe_expert_load_max_over_mean")(record) >= 1.0
    assert run.reader("per_layer", "moe_pack_efficiency_pct")(record) > 50
    # needed operations follow the slots that reached the held experts
    cfg = record["config"]
    tokens = record["traffic"]["batch_per_chip"] * record["traffic"]["seq_len"]
    bare = flops_moe_lm.matmul_flops(cfg, tokens, 0)
    assert bare < window["flops_per_step"] < 1.5 * flops_moe_lm.matmul_flops(cfg, tokens, 4 * tokens)


def test_step_that_returns_its_state_unchanged_reads_one(toy, tmp_path, monkeypatch):
    broken = _run(toy, tmp_path / "broken", True, monkeypatch)
    assert not broken["check_ok"]
    assert broken["check"]["change_gap"] > 0.99 and broken["check"]["grad_gap"] > 0.99
    assert broken["check"]["dir_gap"] > 0.99


def test_float8_control_is_not_correct(toy, tmp_path):
    import jax

    from benchmarks.reference import moe_lm as reference

    spec = _spec(toy, tmp_path)
    rng = np.random.default_rng(5)
    rows, seq = 2, spec["traffic"]["seq_len"] + 1
    seg = np.repeat(np.array([[1] * 100 + [2] * 120 + [0] * (seq - 220)]), rows, axis=0)
    pos = np.concatenate([np.arange(100), np.arange(120), np.zeros(seq - 220, int)])
    batch = {"tokens": rng.integers(3, spec["config"]["vocab_size"], (rows, seq)).astype(np.int32),
             "segment_ids": seg.astype(np.int32), "positions": np.repeat(pos[None], rows, axis=0).astype(np.int32)}
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
    # leaves whose gradient the reference's AdamW cannot see are left out: the first sub-layer's pre and mixing
    # maps (the streams are still equal there), the last sub-layer's mixing map (the streams are summed next) and
    # the selection biases (no gradient reaches them)
    left_out = set(map("/".join, reference.leaf_shapes(spec["config"]))) - set(want["first_grad"])
    assert left_out == set(want_left_out(spec["config"]))
    assert set(want["param_change"]) == set(want["first_grad"]) == set(want["first_grad_sketch"])
    # the routers' matrices are frozen: their gradient is read, and they stay where they are
    assert want["first_grad"]["layer_1/moe/router"] > 0 and want["param_change"]["layer_1/moe/router"] == 0
    assert want["param_change"]["layer_1/moe/experts_up"] > 0


def want_left_out(cfg):
    maps = ["layer_0/res_attn/{}_{}".format(kind, name) for kind in ("phi", "alpha", "b") for name in ("pre", "res")]
    maps += ["layer_{}/res_mlp/{}_res".format(cfg["num_hidden_layers"] - 1, kind) for kind in ("phi", "alpha", "b")]
    return maps + ["layer_{}/moe/router_bias".format(i) for i in range(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])]


def test_balanced_bias_evens_the_routing_and_follows_the_seed(toy, tmp_path):
    import jax

    from benchmarks.reference import moe_lm as reference

    cfg = _spec(toy, tmp_path)["config"]
    rng = np.random.default_rng(6)
    rows, seq = 2, 257
    # a Zipf law's frequent words, as the cell's corpus has them
    words = np.minimum(rng.zipf(1.3, (rows, seq)), cfg["vocab_size"] - 1).astype(np.int32)
    batch = {"tokens": words, "segment_ids": np.ones((rows, seq), np.int32),
             "positions": np.repeat(np.arange(seq, dtype=np.int32)[None], rows, axis=0)}

    def loads(key, bias):
        """Every routed layer's slots per expert on ``batch`` under ``bias`` (None: the seeded one)."""
        params = reference.init_params(key, cfg, bias)
        tokens, seg, pos = batch["tokens"][:, :-1], batch["segment_ids"][:, :-1], batch["positions"][:, :-1]
        x = params["embed"]["embedding"][tokens]
        streams = jax.numpy.broadcast_to(x[:, :, None, :], x.shape[:2] + (cfg["hc_mult"], x.shape[-1]))
        found = []

        def routed(h, p, cfg, quant):
            weights = reference.routing(h.reshape(-1, h.shape[-1]), p, cfg, quant)
            found.append(np.asarray((weights > 0).sum(axis=0)))
            return reference.experts(h, p, cfg, quant)

        with jax.default_matmul_precision("highest"):
            for i in range(cfg["num_hidden_layers"]):
                streams = reference.layer_forward(
                    streams, params["layer_{}".format(i)], pos, seg, cfg, routed=routed)
        return np.stack(found)

    biases = {}
    for seed in (7, 8):
        key = jax.random.PRNGKey(seed)
        biases[seed] = bias = jax.device_get(reference.balanced_bias(key, cfg, batch))
        assert sorted(bias) == ["layer_1", "layer_2"] and bias["layer_1"].shape == (cfg["router_experts"],)
        seeded, balanced = loads(key, None), loads(key, bias)
        mean = seeded.sum(axis=1, keepdims=True) / cfg["router_experts"]
        assert (balanced.sum(axis=1) == seeded.sum(axis=1)).all()  # nothing dropped, nothing added
        assert (np.abs(balanced - mean) / mean).max() < 0.1 < (np.abs(seeded - mean) / mean).max()
    assert not np.allclose(biases[7]["layer_1"], biases[8]["layer_1"])


def test_model_config_is_the_published_one_with_the_share_named():
    _, _cell, config, traffic = run.resolve(CELL)
    family = importlib.import_module("benchmarks.families.moe_lm")
    model = family.model_config(config, traffic["remat"])
    assert model["n_routed_experts"] == 64 and model["experts_held"] == [0, 8] and model["remat"] is True
    assert model["rope_scaling"] == config["source_config"]["rope_scaling"]
    from tensorflowonspark_tpu.models import decoder

    cfg = decoder.DecoderConfig.from_dict(model)
    assert cfg.plan == (("mla", "swiglu", "mhc"),) + (("mla", "moe", "mhc"),) * 4
    assert traffic["seq_len"] == 8192 and traffic["corpus"]["seed"] == 26 and traffic["corpus"]["doc_tokens"]["max"] == 8192


# ---- flops_moe_lm against a count by hand ---------------------------------------------------------

HAND = {"hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 4, "qk_rope_head_dim": 2, "v_head_dim": 4,
        "q_lora_rank": 3, "kv_lora_rank": 5, "hc_mult": 2, "intermediate_size": 16, "moe_intermediate_size": 6,
        "n_shared_experts": 1, "router_experts": 10, "experts_held": [0, 2], "vocab_size": 32,
        "num_hidden_layers": 3, "first_k_dense_replace": 1, "num_experts_per_tok": 2}


def test_flops_by_hand():
    m = flops_moe_lm.macs_per_token(HAND)
    # q_a 8x3, q_b 3x2x6, kv_a 8x7, kv_b 5x2x8, o 2x4x8
    assert m["attention_proj"] == 24 + 36 + 56 + 80 + 64
    assert m["hyper_maps"] == 2 * (2 * 8) * (2 + 2 + 4)  # two sub-layers, n d x (n + n + n n)
    assert m["dense_mlp"] == 3 * 8 * 16 and m["shared_expert"] == 3 * 8 * 6 and m["router"] == 80 and m["head"] == 256
    assert flops_moe_lm.expert_macs_per_slot(HAND) == 144 and flops_moe_lm.layers(HAND) == (1, 2)
    per_token = 3 * (260 + 256) + 384 + 2 * (144 + 80) + 256
    assert flops_moe_lm.matmul_flops(HAND, 10, 7) == 6 * (per_token * 10 + 144 * 7)
    # a pair: scores over 6, values over 4, two heads; forward + twice that backward; three layers
    assert flops_moe_lm.attention_flops(HAND, 100) == 3 * 3 * 2 * (6 + 4) * 2 * 100
    assert flops_moe_lm.flash_bytes(HAND, 2, 16) == 3 * 2 * 16 * 2 * (4 * 6 + 4 * 4) * 2
    assert flops_moe_lm.expert_flops(HAND, 7) == 6 * 144 * 7
    assert flops_moe_lm.expert_bytes(HAND, 7) == (2 * 2 * 3 * 48 * 3 + 2 * (16 + 12) * 7) * 2


def test_published_widths_need_what_the_issue_reckoned():
    _, _cell, config, _traffic = run.resolve(CELL)
    m = flops_moe_lm.macs_per_token(config)
    assert m["attention_proj"] == pytest.approx(28.41e6, rel=1e-3)
    assert m["attention_proj"] + m["hyper_maps"] + m["dense_mlp"] == pytest.approx(128.2e6, rel=2e-3)
    assert flops_moe_lm.expert_macs_per_slot(config) == pytest.approx(11.01e6, rel=1e-3)
    # 2.22 GFLOP a token with half a held expert a token in each routed layer
    assert flops_moe_lm.matmul_flops(config, 1, 4 * 0.5) == pytest.approx(2.22e9, rel=1e-2)
    assert flops_moe_lm.attention_flops(config, 1) == 307200


# ---- the readers on hand-made runs ----------------------------------------------------------------

MLA = "jit(tos_train_step)/tos.loss_and_grad/jvp(Decoder)/layer_1/attn/tos.mla/dot_general"
MLA_BWD = "jit(tos_train_step)/tos.loss_and_grad/transpose(jvp(Decoder))/layer_1/attn/tos.mla/flash_bwd_dq_seg/pallas_call"
ROUTE = "jit(tos_train_step)/tos.loss_and_grad/jvp(Decoder)/layer_1/moe/tos.moe_route/gather"
EXPERTS = "ragged-dot-none"  # XLA's own name for the grouped product's kernel: no scope
SILU = "jit(tos_train_step)/tos.loss_and_grad/jvp(Decoder)/layer_1/moe/tos.moe_experts/mul"
MHC = "jit(tos_train_step)/tos.loss_and_grad/jvp(Decoder)/layer_1/res_mlp/tos.mhc/mul"
OTHER = "jit(tos_train_step)/tos.optimizer/mul"


def _record(counters=None, gauges=None, ops=None):
    _, _cell, config, traffic = run.resolve(CELL)
    record = {
        "workload": CELL, "chips": 1, "config": config, "traffic": traffic, "peak": run._load("peaks.json")["TPU v5 lite"],
        "window": {"seconds": 10.0, "steps": 20, "compiles": 0, "counters": counters or {}, "gauges": gauges or {},
                   "spans": {"bench.next_batch": 0.05}, "counts": {"rows": 20, "real_tokens": 160000, "pairs": 20 * 7.0e6},
                   "flops_per_step": 20e12},
        "trace": None, "step_memory": {"total_bytes": 14.2e9},
    }
    if ops is not None:
        record["trace"] = {"busy_s": 1.0, "window_s": 1.01, "steps": 2,
                           "kernel_s": {"flash_fwd_seg": 0.1, "flash_bwd_dq_seg": 0.05, "flash_bwd_dkv_seg": 0.05}}
        record["_device_ops"] = ({"/device:TPU:0": ops}, (0.0, 1.01))
        record["_phase_shares"] = {"fwd": 25.0, "recompute": 20.0, "bwd": 50.0, "opt": 0.0, "other": 5.0}
    return record


OPS = [(MLA, 0.0, 0.2), (MLA_BWD, 0.2, 0.3), (ROUTE, 0.3, 0.35), (EXPERTS, 0.35, 0.39), (SILU, 0.39, 0.4),
       (MHC, 0.4, 0.55), (OTHER, 0.55, 1.0), (MHC, 2.0, 3.0)]  # the last lies outside the traced window
COUNTED = {"moe_slots_routed_total": 20 * 131072.0, "moe_slots_held_total": 20 * 16384.0,
           "train_step_dispatch_seconds_total": 0.06, "train_steps_dispatched_total": 20.0,
           "train_step_stall_seconds_total": 0.0, "flash_blocks_needed_total": 410.0, "flash_blocks_dense_total": 1000.0,
           "h2d_place_seconds_total": 0.02, "data_consumer_wait_seconds_total": 0.03}


@pytest.mark.parametrize("name,value", [
    ("moe_compiles_in_window", 0), ("moe_input_wait_pct", 0.5), ("moe_pack_efficiency_pct", 160000 / (20 * 8192) * 100),
    ("moe_step_device_ms", 500.0), ("moe_mfu_pct", 100 * 20e12 * 20 / (10 * 197e12)),
    ("moe_device_idle_pct", 100 * (1 - 1 / 1.01)), ("moe_step_hbm_gb", 14.2), ("moe_dispatch_ms_per_step", 3.0),
    ("moe_step_stall_pct", 0.0), ("moe_fwd_pct", 25.0), ("moe_recompute_pct", 20.0), ("moe_bwd_pct", 50.0),
    ("moe_mla_time_pct", 30.0), ("moe_experts_time_pct", 5.0), ("moe_route_time_pct", 5.0), ("moe_mhc_time_pct", 15.0),
    ("moe_slots_held_pct", 12.5), ("moe_expert_load_max_over_mean", 1.25),
    ("moe_flash_time_pct", 20.0), ("moe_flash_blocks_needed_pct", 41.0), ("moe_h2d_place_pct", 0.2),
    ("moe_batch_wait_pct", 0.3),
])
def test_reader_on_a_hand_made_run(name, value):
    record = _record(COUNTED, {"moe_expert_load_max_over_mean": 1.25}, OPS)
    assert run.reader("per_layer", name)(record) == pytest.approx(value, rel=1e-6, abs=1e-9)


def test_rooflines_on_a_hand_made_run():
    record = _record(COUNTED, {}, OPS)
    cfg, peak = record["config"], record["peak"]
    # attention: 7.0e6 pairs a row of one row a step; the three kernels 0.2 s over two steps
    least = max(flops_moe_lm.attention_flops(cfg, 7.0e6) / peak["bf16_flops_per_s"],
                flops_moe_lm.flash_bytes(cfg, 1, 8192) / peak["hbm_bytes_per_s"])
    assert run.reader("per_layer", "moe_mla_flash_roofline_pct")(record) == pytest.approx(100 * least / 0.1)
    # experts: 16384 held slots a step; the grouped product's kernels (not the gate's elementwise) 0.04 s over two steps
    assert _moe.slots_held_per_step(record) == pytest.approx(16384.0)
    least = max(flops_moe_lm.expert_flops(cfg, 16384) / peak["bf16_flops_per_s"],
                flops_moe_lm.expert_bytes(cfg, 16384) / peak["hbm_bytes_per_s"])
    got = run.reader("per_layer", "moe_experts_roofline_pct")(record)
    assert got == pytest.approx(100 * least / 0.02) and got < 100


@pytest.mark.parametrize("name", [
    "moe_mla_time_pct", "moe_experts_time_pct", "moe_route_time_pct", "moe_mhc_time_pct", "moe_mla_flash_roofline_pct",
    "moe_experts_roofline_pct", "moe_slots_held_pct", "moe_expert_load_max_over_mean", "moe_dispatch_ms_per_step",
    "moe_fwd_pct", "moe_step_device_ms", "moe_device_idle_pct", "moe_flash_time_pct", "moe_flash_blocks_needed_pct",
    "moe_h2d_place_pct", "moe_batch_wait_pct",
])
def test_reader_finds_nothing_in_a_program_without_the_scopes_and_counters(name):
    """The parent of the PR that brought them: no counter, no gauge, no trace
    (untraced run) or a trace whose operations carry no such scope."""
    assert run.reader("per_layer", name)(_record()) is None
    unscoped = _record({}, {}, [("jit(tos_train_step)/tos.loss_and_grad/jvp()/dot_general", 0.0, 0.5)])
    unscoped["trace"]["kernel_s"] = {}
    unscoped["_phase_shares"] = None
    if name not in ("moe_step_device_ms", "moe_device_idle_pct"):
        assert run.reader("per_layer", name)(unscoped) is None


def test_scope_match_is_by_whole_scope():
    assert _moe.in_scope(MLA, "tos.mla") and not _moe.in_scope(ROUTE, "tos.moe")
    assert _moe.in_scope("a/tos.mhc", "tos.mhc") and not _moe.in_scope("a/tos.mhc_x/b", "tos.mhc")
