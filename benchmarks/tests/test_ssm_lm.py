"""The ``ssm_lm`` family at sizes a test run can hold: the job through
``child.run_job`` at the toy widths of ``data/ssm_lm_toy.json`` (merged over the
cell's own files, as ``--rehearse`` merges ``rehearse.json``'s entries; the
kernels interpreted), the float8 control, a step that returns its state
unchanged, a program whose scans forget their restarts, the configuration held
to the published one, ``flops_ssm_lm`` and ``scan_bytes`` against counts by
hand, each new reader on a hand-made run."""

import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmarks import check, child, flops_ssm_lm, run, scan_bytes
from benchmarks.layer_metrics import _ssm

CELL = "phi-4-mini-flash.reason8k"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def toy():
    with open(os.path.join(DATA, "ssm_lm_toy.json")) as f:
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
    family = importlib.import_module("benchmarks.families.ssm_lm")
    if broken == "no restart":  # the program's scans run on from one document of a row into the next
        from tensorflowonspark_tpu.ops import selective_scan

        monkeypatch.setattr(selective_scan, "restarts", lambda ids: jnp.zeros(ids.shape, bool).at[:, 0].set(True))

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
    traffic = spec["traffic"]
    tokens = traffic["batch_per_chip"] * traffic["seq_len"]
    assert window["units"] == window["steps"] * tokens
    # the producer counts the scans' positions and restarts from the rows' ids, without a device sync
    counters = window["counters"]
    assert counters["ssm_scan_positions_total"] % tokens == 0 and counters["ssm_scan_restarts_total"] > 0
    restarts = run.reader("per_layer", "ssm_restarts_per_row")(record)
    assert 1 < restarts < 20 and restarts == pytest.approx(
        counters["ssm_scan_restarts_total"] / counters["ssm_scan_positions_total"] * traffic["seq_len"])
    # what crossed layers a step: the memory [2, 256, 256] and k, v [2, 256, 4, 16] each, float32
    assert counters["ssm_state_carried_bytes_total"] % (4 * (2 * 256 * 256 + 2 * 2 * 256 * 64)) == 0
    assert counters["ssm_state_carried_bytes_total"] > 0
    # the accepted readers this cell is listed under read it as they read laguna-s-2-1
    assert run.reader("per_layer", "swa_pack_efficiency_pct")(record) > 50
    assert 0 < run.reader("per_layer", "swa_flash_win_blocks_needed_pct")(record) <= 100
    assert 0 < run.reader("per_layer", "swa_flash_win_pairs_used_pct")(record) < 50  # a window of 48 in a block of 256
    assert 0 < window["counts"]["pairs_window"] < window["counts"]["pairs"]
    assert sound["parts"]["traced_rows"] == traffic["trace_steps"] * traffic["batch_per_chip"]
    assert 0 < sound["parts"]["traced_pairs_window"] < sound["parts"]["traced_pairs"]
    bare = flops_ssm_lm.matmul_flops(spec["config"], tokens)
    assert bare < window["flops_per_step"] < 1.5 * bare


@pytest.mark.parametrize("broken", ["unchanged", "no restart"])
def test_a_broken_program_is_not_correct(toy, tmp_path, monkeypatch, broken):
    """A step that returns its state unchanged reads 1 everywhere; a program
    whose scans carry a document's state into the next fails by its
    gradient's direction (and more)."""
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

    from benchmarks.reference import ssm_lm as reference

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
    # every leaf is heard but the key projections' biases (a softmax row's scores all move alike), and every one trained
    quiet = {"layer_1/attn/k/bias", "layer_3/attn/k/bias"}
    assert set(want["first_grad"]) == set(map("/".join, reference.leaf_shapes(spec["config"]))) - quiet
    assert set(want["param_change"]) == set(want["first_grad"]) == set(want["first_grad_sketch"])
    for leaf in ("embed/embedding", "layer_0/mamba/a_log", "layer_2/mamba/skip", "layer_1/attn/lambda_q1",
                 "layer_3/attn/subln/scale", "layer_4/gmu/in_proj/kernel", "layer_5/attn/q/bias", "ln_f/bias"):
        assert want["first_grad"][leaf] > 0 and want["param_change"][leaf] > 0, leaf


def test_model_config_is_the_published_one_with_the_cut_named():
    _, cell, config, traffic = run.resolve(CELL)
    assert cell["chips"] == 1
    family = importlib.import_module("benchmarks.families.ssm_lm")
    model = family.model_config(config, traffic["remat"])
    assert model["remat"] is True and not set(model) & set(family.BENCHMARK_KEYS)
    published = config["source_config"]
    cut = set(config["reduced"])
    assert cut == {"num_hidden_layers", "vocab_size", "max_position_embeddings"}
    assert all(config[k] == v for k, v in published.items() if k not in cut)
    assert set(config["reduced_why"]) == cut and config["vocab_size"] * 8 >= published["vocab_size"]
    assert config["vocab_size"] % 128 == 0 and (config["first_layer"], config["model_layers"]) == (14, published["num_hidden_layers"])
    assert set(config["assumed"]) >= {"mamba", "split", "differential", "biases", "memory", "window", "init", "optimizer"}
    from benchmarks.reference import ssm_lm as reference
    from tensorflowonspark_tpu.models import decoder

    cfg = decoder.DecoderConfig.from_dict(model)
    assert [kinds[0] for kinds in cfg.plan] == ["mamba", "gqa", "mamba", "gqa", "gmu", "cross"]
    assert [reference.layer_kind(config, i)[0] for i in range(6)] == flops_ssm_lm.layer_kinds(config) == [
        "mamba", "window", "mamba", "full", "gmu", "cross"]
    assert [cfg.heads_plan(i).window for i in range(6)] == [None, 512, None, None, None, None]
    assert [cfg.heads_plan(i).hands_on for i in range(6)] == [False, False, True, True, False, False]
    assert [cfg.heads_plan(i).lambda_init for i in (1, 3, 5)] == pytest.approx([reference.lambda_init(l) for l in (15, 17, 19)])
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.intermediate_size) == (2560, 40, 20, 10240)
    assert (cfg.d_inner, decoder.MAMBA_STATES, cfg.dt_rank, decoder.MAMBA_TAPS) == (5120, 16, 160, 4) == reference.sizes(config)
    assert cfg.tie_word_embeddings and cfg.layer_norm_eps == 1e-5 and not cfg.rotary and cfg.attention_bias
    assert reference.parameter_count(config) == config["parameters"]["here"] == 697299072
    assert traffic["seq_len"] == 8192 and traffic["batch_per_chip"] == 1 and traffic["corpus"]["seed"] == 41
    law = traffic["corpus"]["doc_tokens"]
    assert (law["median"], law["sigma"], law["min"], law["max"]) == (4000, 0.9, 256, 8192)
    assert config["optimizer"]["learning_rate"] == 1e-4 and "frozen" not in config["optimizer"]


# ---- flops_ssm_lm and scan_bytes against counts by hand ---------------------------------------------

HAND = {"hidden_size": 16, "num_attention_heads": 4, "num_key_value_heads": 2, "intermediate_size": 20, "vocab_size": 32,
        "num_hidden_layers": 6, "first_layer": 2, "model_layers": 8, "mb_per_layer": 2, "sliding_window": 4,
        "dtype": "bfloat16"}


def test_flops_by_hand():
    assert flops_ssm_lm.sizes(HAND) == (32, 16, 1)
    assert flops_ssm_lm.layer_kinds(HAND) == ["mamba", "window", "mamba", "full", "gmu", "cross"]
    mamba = 16 * 64 + 32 * (1 + 32) + 1 * 32 + 32 * 16
    attention = 16 * (4 * 4 + 2 * 2 * 4) + 16 * 16  # q, k, v; the output's [2 pairs x 8, 16]
    cross = 16 * 16 + 16 * 16
    gmu = 2 * 16 * 32
    assert [flops_ssm_lm.mixer_macs_per_token(HAND, k) for k in ("mamba", "full", "window", "cross", "gmu")] == [
        mamba, attention, attention, cross, gmu]
    assert flops_ssm_lm.macs_per_token(HAND) == 16 * 32 + 2 * mamba + 2 * attention + cross + gmu + 6 * 3 * 16 * 20
    assert flops_ssm_lm.matmul_flops(HAND, 10) == 6 * 10 * flops_ssm_lm.macs_per_token(HAND)
    # a pair and query head: a score over 4 and a value product over 8, forward; three times that with the backward
    a_pair = 3 * 2 * (4 + 8) * 4
    assert flops_ssm_lm.attention_flops(HAND, 100, 30) == a_pair * (2 * 100 + 30)  # the full and the cross layer, the windowed one
    assert flops_ssm_lm.attention_flops(HAND, 100, 30, ("window",)) == a_pair * 30
    assert flops_ssm_lm.attention_flops(HAND, 100, 30, ("full", "cross")) == a_pair * 200
    # q, dq 4 x 4; o, do 4 x 8; k, dk 2 x 4; V_j, dV_j 1 x 8: bfloat16
    a_layer = 2 * 16 + 2 * 32 + 2 * 8 + 2 * 8
    assert flops_ssm_lm.flash_bytes(HAND, 2, 16, ("window",)) == 2 * 16 * a_layer * 2
    assert flops_ssm_lm.flash_bytes(HAND, 2, 16, ("full", "cross")) == 2 * 16 * 2 * a_layer * 2
    # the scans: dt, c in and y out at 32 channels, B and C at 16 states; the backward's twins and dy
    assert scan_bytes.layer_bytes(HAND, 2, 16) == (2 * 16 * 2 * (3 * 32 + 32), 2 * 16 * 2 * (5 * 32 + 64))
    assert scan_bytes.step_bytes(HAND, 2, 16) == 2 * sum(scan_bytes.layer_bytes(HAND, 2, 16))


def test_published_widths_need_what_the_issue_reckoned():
    _, _cell, config, _traffic = run.resolve(CELL)
    # 697.3M parameters of which the embedding is read and multiplied once a token as the head
    assert flops_ssm_lm.macs_per_token(config) == pytest.approx(697.3e6, rel=2e-3)
    assert flops_ssm_lm.matmul_flops(config, 8192) == pytest.approx(34.3e12, rel=5e-3)  # the issue's 6 x 697M x 8192
    assert 6 * 3 * 2560 * 10240 / flops_ssm_lm.macs_per_token(config) == pytest.approx(0.68, abs=0.01)
    # a full triangle of one document of 8192: 40 maps, scores at 64 and values at 128, in two layers
    pairs = 8192 * 8193 // 2
    assert flops_ssm_lm.attention_flops(config, pairs, 0) == pytest.approx(3.09e12, rel=1e-2)
    # a step's scans: 8 x 5120 + 6 x 16 values a position, 2 bytes each, two layers
    assert scan_bytes.step_bytes(config, 1, 8192) == 2 * 8192 * 2 * (8 * 5120 + 6 * 16) == pytest.approx(1.345e9, rel=1e-3)


def test_visible_pairs_are_the_masks():
    from benchmarks.reference import ssm_lm as reference

    batch = _batch(2, 256, 3)
    batch["segment_ids"][1, 100:103] = 0
    seg = batch["segment_ids"][:, :-1]
    at = np.broadcast_to(np.arange(seg.shape[1])[None], seg.shape)
    for window in (None, 1, 48, 4096):
        assert flops_ssm_lm.visible_pairs(seg, window) == int(np.asarray(reference.visible(seg, at, seg, at, window)).sum())


# ---- the readers on hand-made runs ----------------------------------------------------------------

STEP = "jit(tos_train_step)/tos.loss_and_grad/"
MAMBA = STEP + "jvp(Decoder)/layer_0/mamba/tos.mamba/in_proj/dot_general"
CONV = STEP + "jvp(Decoder)/layer_0/mamba/tos.mamba/tos.ssm_conv/mul"
SCAN = STEP + "jvp(Decoder)/layer_0/mamba/tos.mamba/tos.ssm_scan/ssm_scan_fwd/pallas_call"
SCAN_BWD = STEP + "transpose(jvp(Decoder))/layer_0/mamba/tos.mamba/tos.ssm_scan/ssm_scan_bwd/pallas_call"
GMU = STEP + "jvp(Decoder)/layer_4/gmu/tos.gmu/out_proj/dot_general"
CROSS = STEP + "jvp(Decoder)/layer_5/attn/tos.cross_attn/q/dot_general"
CROSS_DIFF = STEP + "jvp(Decoder)/layer_5/attn/tos.cross_attn/tos.diff_attn/sub"
FULL_DIFF = STEP + "jvp(Decoder)/layer_3/attn/tos.gqa/tos.diff_attn/subln/mul"
OTHER = "jit(tos_train_step)/tos.optimizer/mul"
OPS = [(MAMBA, 0.0, 0.1), (CONV, 0.1, 0.12), (SCAN, 0.12, 0.15), (SCAN_BWD, 0.15, 0.2), (GMU, 0.2, 0.26),
       (CROSS, 0.26, 0.3), (CROSS_DIFF, 0.3, 0.31), (FULL_DIFF, 0.31, 0.33), (OTHER, 0.33, 1.0),
       (MAMBA, 2.0, 3.0)]  # the last lies outside the traced window


def _record(counters=None, ops=None):
    _, _cell, config, traffic = run.resolve(CELL)
    record = {
        "workload": CELL, "chips": 1, "config": config, "traffic": traffic, "peak": run._load("peaks.json")["TPU v5 lite"],
        "window": {"seconds": 10.0, "steps": 25, "compiles": 0, "counters": counters or {}, "gauges": {},
                   "spans": {"bench.next_batch": 0.05}, "counts": {"rows": 25, "real_tokens": 195000},
                   "flops_per_step": 36e12},
        "trace": None, "step_memory": {"total_bytes": 12.87e9},
        "parts": {"traced_rows": 2, "traced_pairs": 2 * 17e6, "traced_pairs_window": 2 * 3.9e6},
    }
    if ops is not None:
        record["trace"] = {"busy_s": 1.0, "window_s": 1.01, "steps": 2,
                           "kernel_s": {"ssm_scan_fwd": 0.03, "ssm_scan_bwd": 0.05, "flash_fwd_win": 0.004,
                                        "flash_bwd_dkv_win": 0.009, "flash_fwd_seg": 0.014, "flash_bwd_dkv_seg": 0.032}}
        record["_device_ops"] = ({"/device:TPU:0": ops}, (0.0, 1.01))
    return record


COUNTED = {"ssm_scan_positions_total": 25 * 8192.0, "ssm_scan_restarts_total": 60.0}


@pytest.mark.parametrize("name,value", [
    ("ssm_mamba_time_pct", 20.0), ("ssm_conv_time_pct", 2.0), ("ssm_scan_time_pct", 8.0), ("ssm_gmu_time_pct", 6.0),
    ("ssm_cross_attn_time_pct", 5.0), ("ssm_diff_time_pct", 3.0), ("ssm_restarts_per_row", 2.4),
])
def test_reader_on_a_hand_made_run(name, value):
    assert run.reader("per_layer", name)(_record(COUNTED, OPS)) == pytest.approx(value, rel=1e-6)


def test_rooflines_on_a_hand_made_run():
    record = _record(COUNTED, OPS)
    cfg, peak = record["config"], record["peak"]
    # the scans: 1.345 GB a step at 819 GB/s is 1.64 ms; the two kernels took 0.08 s over the two traced steps
    got = run.reader("per_layer", "ssm_scan_roofline_pct")(record)
    assert got == pytest.approx(100 * scan_bytes.step_bytes(cfg, 1, 8192) / 819e9 / 0.04) and 3 < got < 6
    # the windowed layer: the traced rows' 3.9e6 pairs a row; its two kernels 0.013 s over the two steps
    least = max(flops_ssm_lm.attention_flops(cfg, 34e6, 7.8e6, ("window",)) / peak["bf16_flops_per_s"],
                flops_ssm_lm.flash_bytes(cfg, 2, 8192, ("window",)) / peak["hbm_bytes_per_s"])
    got = run.reader("per_layer", "ssm_flash_win_roofline_pct")(record)
    assert got == pytest.approx(100 * least / 0.013) and got < 100
    # the full and the cross layer: 17e6 pairs a row and layer; the causal kernels 0.046 s over the two steps
    least = max(flops_ssm_lm.attention_flops(cfg, 34e6, 7.8e6, ("full", "cross")) / peak["bf16_flops_per_s"],
                flops_ssm_lm.flash_bytes(cfg, 2, 8192, ("full", "cross")) / peak["hbm_bytes_per_s"])
    got = run.reader("per_layer", "ssm_flash_full_roofline_pct")(record)
    assert got == pytest.approx(100 * least / 0.046) and got < 100
    record["parts"] = {}
    assert run.reader("per_layer", "ssm_flash_full_roofline_pct")(record) is None


@pytest.mark.parametrize("name", [
    "ssm_mamba_time_pct", "ssm_conv_time_pct", "ssm_scan_time_pct", "ssm_scan_roofline_pct", "ssm_gmu_time_pct",
    "ssm_cross_attn_time_pct", "ssm_diff_time_pct", "ssm_flash_win_roofline_pct", "ssm_flash_full_roofline_pct",
    "ssm_restarts_per_row",
])
def test_reader_finds_nothing_in_a_program_without_the_scopes_and_counters(name):
    """The parent of the PR that brought them: no counter, no trace (untraced run), or a trace whose
    operations carry no such scope and whose kernels are another family's."""
    assert run.reader("per_layer", name)(_record()) is None
    unscoped = _record({}, [("jit(tos_train_step)/tos.loss_and_grad/jvp()/dot_general", 0.0, 0.5)])
    unscoped["trace"]["kernel_s"] = {"flash_fwd_bd": 0.1}
    assert run.reader("per_layer", name)(unscoped) is None
    # another family's cell, whose configuration has no mb_per_layer: nothing to set the kernels against
    other = _record(COUNTED, OPS)
    other["config"] = {"hidden_size": 8}
    if "roofline" in name:
        assert run.reader("per_layer", name)(other) is None
    assert _ssm.kernel_seconds(unscoped, _ssm.SCAN_KERNELS) is None
