"""The ``ssd_lm`` family at sizes a test run can hold: the job through
``child.run_job`` at the toy widths of ``data/ssd_lm_toy.json`` (merged over the
cell's own files, as ``--rehearse`` merges ``rehearse.json``'s entries; the
kernels interpreted), the float8 control, a step that returns its state
unchanged, a program whose scans ignore the documents, one that leaves out the
latent's way up, the configuration held to the published one, each new reader
on a hand-made run, and ``flops_ssd_lm`` / ``ssd_ops`` against a count by hand."""

import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmarks import check, child, flops_ssd_lm, run, ssd_ops
from benchmarks.layer_metrics import _ssd

CELL = "nemotron-3-super.agent8k"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def toy():
    with open(os.path.join(DATA, "ssd_lm_toy.json")) as f:
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
    family = importlib.import_module("benchmarks.families.ssd_lm")
    if broken == "no restart":  # the program's scans run on from one document of a row into the next
        from tensorflowonspark_tpu.ops import ssd_scan

        real_scan = ssd_scan.ssd_scan
        monkeypatch.setattr(ssd_scan, "ssd_scan", lambda *a, **kw: real_scan(*a[:6], None, *a[7:], **kw))

    def build(spec, ctx, parts):
        job = family.build(spec, ctx, parts)
        if broken == "unchanged":
            real = job.step

            def unchanged(state, batch):
                _, metrics = real(jax.tree.map(jnp.copy, state), batch)
                return state, metrics

            job.step = _Callable(unchanged, real)
        if broken == "no way up":  # the routed experts' sum never leaves the latent: W_up is nothing
            params = jax.tree.map(lambda x: x, job.state.params)
            for layer in params:
                if "moe" in params[layer]:
                    params[layer]["moe"]["latent_up"]["kernel"] = jnp.zeros_like(params[layer]["moe"]["latent_up"]["kernel"])
            job.state = job.state.replace(params=params)
        return job

    return child.run_job(_spec(toy, scratch), _ctx(), 0.0, build=build, out=lambda line: None)


def test_sound_run_passes_and_counts(toy, tmp_path, monkeypatch):
    sound = _run(toy, tmp_path / "sound", None, monkeypatch)
    assert sound["check_ok"] and not sound["correct"], sound["check"]  # a rehearsal never reports correct
    window = sound["window"]
    assert window["steps"] >= 1 and window["compiles"] == 0
    spec = _spec(toy, tmp_path)
    record = dict(sound, workload=CELL, chips=1, config=spec["config"], traffic=spec["traffic"])
    traffic, counters = spec["traffic"], window["counters"]
    tokens = traffic["batch_per_chip"] * traffic["seq_len"]
    assert window["units"] == window["steps"] * tokens
    # the producer counts the scans' positions and restarts from the rows' ids, without a device sync
    assert counters["ssm_scan_positions_total"] % tokens == 0 and counters["ssm_scan_restarts_total"] > 0
    assert 1 < run.reader("per_layer", "ssd_restarts_per_row")(record) < 20
    # two Mamba-2 blocks, 2 rows of 256 in chunks of 16, every booked step
    assert counters["ssd_scan_chunks_total"] % (2 * 2 * 16) == 0 and counters["ssd_scan_chunks_total"] > 0
    # 4 of 16 experts held: 25 under even routing, which the balanced bias aims at on the first batch's real positions
    assert 19 < run.reader("per_layer", "swa_slots_held_pct")(record) * tokens * window["steps"] / window["counts"][
        "real_tokens"] < 31
    assert sound["parts"]["balance_s"] > 0
    assert run.reader("per_layer", "swa_layers_compact_pct")(record) == 100.0
    assert run.reader("per_layer", "swa_pack_efficiency_pct")(record) > 50
    assert run.reader("per_layer", "swa_mfu_pct")(record) is None  # no peak in a rehearsal
    assert sound["parts"]["traced_rows"] == traffic["trace_steps"] * traffic["batch_per_chip"]
    assert 0 < sound["parts"]["traced_pairs"]
    routed_a_step = flops_ssd_lm.slots_per_step(spec["config"], traffic["batch_per_chip"], traffic["seq_len"])
    assert routed_a_step == tokens * 4 * 1 and counters["moe_slots_routed_total"] % routed_a_step == 0
    bare = flops_ssd_lm.matmul_flops(spec["config"], tokens, 0)
    assert bare < window["flops_per_step"] < 1.5 * bare


@pytest.mark.parametrize("broken", ["unchanged", "no restart", "no way up"])
def test_a_broken_program_is_not_correct(toy, tmp_path, monkeypatch, broken):
    """A step that returns its state unchanged reads 1 everywhere; a program
    whose scans carry a document's state into the next, and one whose routed
    experts' sum never comes back up from the latent, fail by the gradient's
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


def test_three_adamw_steps_and_the_float8_control(toy, tmp_path):
    """The reference follows three steps; the float8 control of the same steps is not correct, by the toy's limits
    and by the cell's."""
    import jax

    from benchmarks.reference import ssd_lm as reference

    spec = _spec(toy, tmp_path)
    cfg = spec["config"]
    batches = [_batch(2, spec["traffic"]["seq_len"], seed) for seed in (5, 6, 7)]
    key, devices = jax.random.PRNGKey(7), jax.devices()[:1]
    want = reference.follow(cfg, key, batches, devices)
    control = reference.follow(cfg, key, batches, devices, quant="fp8")
    read = check.readings(control, want)
    for limits in (toy["limits"], check.load_limits(CELL)):
        correct, _ = check.judge(read, limits)
        assert not correct, read
        assert read["dir_gap"] > 2 * limits["dir_gap"], read
        same, _ = check.judge(check.readings(want, want), limits)
        assert same
    assert len(want["losses"]) == 3 and all(np.isfinite(want["losses"]))
    # every leaf but the selection biases is heard; the routers are frozen: their gradient is read, they stay
    heard = set(map("/".join, reference.leaf_shapes(cfg))) - {"layer_1/moe/router_bias"}
    assert set(want["first_grad"]) == set(want["param_change"]) == set(want["first_grad_sketch"]) == heard
    assert want["first_grad"]["layer_1/moe/router"] > 0 and want["param_change"]["layer_1/moe/router"] == 0
    for leaf in ("layer_0/mamba2/a_log", "layer_0/mamba2/dt_bias", "layer_2/mamba2/conv_kernel", "layer_3/attn/k/kernel",
                 "layer_1/moe/latent_up/kernel", "layer_1/moe/experts_down", "layer_1/moe/shared/up/kernel"):
        assert want["param_change"][leaf] > 0, leaf


def test_model_config_is_the_published_one_with_the_shares_named():
    _, cell, config, traffic = run.resolve(CELL)
    assert cell["chips"] == 1
    family = importlib.import_module("benchmarks.families.ssd_lm")
    model = family.model_config(config, traffic["remat"])
    assert model["n_routed_experts"] == 512 and model["experts_held"] == [0, 8] and model["heads_held"] == [0, 4]
    assert (model["mamba_num_heads"], model["n_groups"], model["num_attention_heads"], model["num_key_value_heads"]) == (
        128, 8, 32, 2)
    assert model["remat"] is True and model["padding_slots"] is False
    published = config["source_config"]
    cut = set(config["reduced"])
    assert cut == {"num_hidden_layers", "mamba_num_heads", "n_groups", "num_attention_heads", "num_key_value_heads",
                   "n_routed_experts", "vocab_size", "num_nextn_predict_layers", "max_position_embeddings"}
    assert all(config[k] == v for k, v in published.items() if k not in cut)
    assert set(config["reduced_why"]) == cut and config["vocab_size"] * 8 == published["vocab_size"]
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "nemotron-3-super")
    assert set(entry["reduced"]) == cut and entry["source"] == config["source"]
    from benchmarks.reference import ssd_lm as reference
    from tensorflowonspark_tpu.models import decoder

    cfg = decoder.DecoderConfig.from_dict(model)
    kinds = {"M": ("mamba2", None, "add"), "E": (None, "moe", "add"), "*": ("gqa", None, "add")}
    assert cfg.plan == tuple(kinds[letter] for letter in "MEMEMEMEM*E")
    assert (cfg.first_layer, cfg.depth, cfg.hidden_size, cfg.rms_norm_eps) == (27, 88, 4096, 1e-5)
    # every published width: Mamba head 64, state 128, convolution 4, chunk 128, attention head 128, latent 1024,
    # expert 2688, shared 5376, router 512 wide, top-22, scaling 5
    assert (cfg.mamba_head_dim, cfg.ssm_state_size, cfg.conv_kernel, cfg.chunk_size, cfg.head_dim) == (64, 128, 4, 128, 128)
    assert (cfg.moe_latent_size, cfg.moe_intermediate_size, cfg.shared_width, cfg.mlp_hidden_act) == (1024, 2688, 5376, "relu2")
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.routed_scaling_factor, cfg.scoring_func) == (512, 22, 5, "sigmoid")
    # the shares held: 32 heads in 2 groups, 8 query heads on 1 key/value head
    assert cfg.share_of(cfg.mamba_num_heads, "") == 32 and cfg.share_of(cfg.n_groups, "") == 2
    assert cfg.heads_plan(9).heads == 8 and cfg.kv_heads == 1 and not cfg.rotary and not cfg.qk_norm
    assert reference.parameter_count(config) == config["parameters"]["here"] == 773582304
    assert traffic["seq_len"] == 8192 and traffic["batch_per_chip"] == 1 and traffic["corpus"]["seed"] == 45
    law = traffic["corpus"]["doc_tokens"]
    assert (law["median"], law["sigma"], law["min"], law["max"]) == (2000, 1.1, 64, 8192)


def test_the_built_tree_is_the_stated_parameter_count():
    """The program's own parameter tree at the cell's widths (shapes only) holds what the file states."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import models

    _, _cell, config, traffic = run.resolve(CELL)
    family = importlib.import_module("benchmarks.families.ssd_lm")
    model = models.get_model("decoder", **family.model_config(config, traffic["remat"]))
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))["params"]
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)) == config["parameters"]["here"]
    from benchmarks.reference import ssd_lm as reference

    ours = {"/".join(path): shape for path, (shape, _) in reference.leaf_shapes(config).items()}
    theirs = {"/".join(str(p.key) for p in path): leaf.shape for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert ours == theirs


# ---- flops_ssd_lm and ssd_ops against a count by hand ---------------------------------------------

HAND = {"hidden_size": 8, "vocab_size": 32, "hybrid_override_pattern": "EM*EM", "first_layer": 1, "num_hidden_layers": 3,
        "mamba_num_heads": 4, "n_groups": 2, "mamba_head_dim": 3, "ssm_state_size": 5, "chunk_size": 4,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 3, "router_experts": 10, "experts_held": [0, 2],
        "num_experts_per_tok": 2, "moe_latent_size": 6, "moe_intermediate_size": 7,
        "moe_shared_expert_intermediate_size": 9, "dtype": "bfloat16"}


def test_flops_and_bytes_by_hand():
    assert flops_ssd_lm.block_kinds(HAND) == "M*E"
    mamba = 8 * (2 * 12 + 2 * 10 + 4) + 12 * 8
    attention = 2 * 8 * 6 + 2 * 8 * 3
    experts = 8 * 10 + 2 * 8 * 6 + 2 * 8 * 9
    assert flops_ssd_lm.macs_per_token(HAND) == 8 * 32 + mamba + attention + experts
    assert flops_ssd_lm.expert_macs_per_slot(HAND) == 2 * 6 * 7 and flops_ssd_lm.slots_per_step(HAND, 2, 16) == 2 * 16 * 2
    assert flops_ssd_lm.matmul_flops(HAND, 10, 12) == 6 * (10 * flops_ssd_lm.macs_per_token(HAND) + 84 * 12)
    assert flops_ssd_lm.attention_flops(HAND, 100) == 6 * 2 * 3 * 2 * 100
    assert flops_ssd_lm.expert_flops(HAND, 7) == 6 * 84 * 7
    assert flops_ssd_lm.expert_bytes(HAND, 7) == (1 * 2 * 2 * 42 * 3 + 2 * (12 + 7) * 7) * 2
    # a chunk of 4 and a head of 3 x 5: its own map 2 x 4 x 4 x 3, the state read and written 2 x 2 x 4 x 5 x 3;
    # C B^T 2 x 4 x 4 x 5 a group; 2 rows of 16 are 8 chunks; forward + twice that backward
    a_chunk = 4 * (2 * 16 * 3 + 4 * 4 * 5 * 3) + 2 * 2 * 16 * 5
    assert ssd_ops.layer_flops(HAND, 2, 16) == 3 * 8 * a_chunk == ssd_ops.step_flops(HAND, 2, 16)
    # a position: x and y 12 values, B and C 10 each, bfloat16, the step 4 float32; the backward's twins and dy;
    # 4 heads x 4 chunks x 3 x 5 float32 states a row, once each way
    forward, backward = 2 * 24 + 16 + 2 * 20, (2 * 24 + 16 + 2 * 20) + (24 + 16 + 2 * 20)
    assert ssd_ops.layer_bytes(HAND, 2, 16) == 2 * 16 * (forward + backward) + 2 * 2 * 4 * 4 * 15 * 4
    assert ssd_ops.step_bytes(HAND, 2, 17) > ssd_ops.step_bytes(HAND, 2, 16)  # a row's last chunk counts whole


def test_published_widths_need_what_the_issue_reckoned():
    _, _cell, config, _traffic = run.resolve(CELL)
    # some 486M parameters meet each token outside the routed experts, 495M with a held expert's 0.34 slots a block
    assert flops_ssd_lm.macs_per_token(config) == pytest.approx(486.2e6, rel=2e-3)
    held = 5 * 8 * 352
    assert flops_ssd_lm.matmul_flops(config, 8192, held) == pytest.approx(24.4e12, rel=1e-2)
    assert flops_ssd_lm.expert_flops(config, held) / flops_ssd_lm.matmul_flops(config, 8192, held) < 0.025
    assert 5 * flops_ssd_lm.mamba2_macs_per_token(config) == pytest.approx(137.0e6, rel=1e-3)
    assert flops_ssd_lm.expert_macs_per_slot(config) == 2 * 1024 * 2688
    # the five scans of a step in chunked form: 0.2 TFLOP and 1.65 GB, bound by the bytes at 819 GB/s
    assert ssd_ops.step_flops(config, 1, 8192) == pytest.approx(0.2013e12, rel=1e-3)
    assert ssd_ops.step_bytes(config, 1, 8192) == pytest.approx(1.652e9, rel=1e-2)
    assert ssd_ops.step_bytes(config, 1, 8192) / 819e9 > ssd_ops.step_flops(config, 1, 8192) / 197e12


# ---- the readers on hand-made runs ----------------------------------------------------------------

STEP = "jit(tos_train_step)/tos.loss_and_grad/"
MAMBA = STEP + "jvp(Decoder)/layer_0/mamba2/tos.mamba2/in_proj/dot_general"
CONV = STEP + "jvp(Decoder)/layer_0/mamba2/tos.mamba2/tos.ssm_conv/mul"
SCAN = STEP + "jvp(Decoder)/layer_0/mamba2/tos.mamba2/tos.ssd_scan/ssd_scan_fwd/pallas_call"
SCAN_BWD = STEP + "transpose(jvp(Decoder))/layer_0/mamba2/tos.mamba2/tos.ssd_scan/ssd_scan_bwd/pallas_call"
SCAN_XLA = STEP + "jvp(Decoder)/layer_0/mamba2/tos.mamba2/tos.ssd_scan/cumsum"
OLD_CONV = STEP + "jvp(Decoder)/layer_0/mamba/tos.mamba/tos.ssm_conv/mul"  # another dialect's convolution
LATENT = STEP + "jvp(Decoder)/layer_1/moe/tos.moe_latent/latent_down/dot_general"
LATENT_BWD = STEP + "transpose(jvp(Decoder))/layer_1/moe/tos.moe_latent/latent_up/dot_general"
EXPERTS = "ragged-dot-none"
OTHER = "jit(tos_train_step)/tos.optimizer/mul"
OPS = [(MAMBA, 0.0, 0.1), (CONV, 0.1, 0.12), (SCAN, 0.12, 0.13), (SCAN_BWD, 0.13, 0.16), (SCAN_XLA, 0.16, 0.17),
       (OLD_CONV, 0.17, 0.18), (LATENT, 0.18, 0.2), (LATENT_BWD, 0.2, 0.24), (EXPERTS, 0.24, 0.26), (OTHER, 0.26, 1.0),
       (MAMBA, 2.0, 3.0)]  # the last lies outside the traced window
ROUTED = 25 * 8192 * 22 * 5.0
COUNTED = {"ssm_scan_positions_total": 25 * 8192.0, "ssm_scan_restarts_total": 90.0,
           "moe_slots_routed_total": ROUTED, "moe_slots_held_total": ROUTED / 64}


def _record(counters=None, ops=None):
    _, _cell, config, traffic = run.resolve(CELL)
    record = {
        "workload": CELL, "chips": 1, "config": config, "traffic": traffic, "peak": run._load("peaks.json")["TPU v5 lite"],
        "window": {"seconds": 10.0, "steps": 25, "compiles": 0, "counters": counters or {}, "gauges": {},
                   "spans": {"bench.next_batch": 0.05}, "counts": {"rows": 25, "real_tokens": 195000},
                   "flops_per_step": 25e12},
        "trace": None, "step_memory": {"total_bytes": 14.0e9}, "parts": {"traced_rows": 2, "traced_pairs": 2 * 9e6},
    }
    if ops is not None:
        record["trace"] = {"busy_s": 1.0, "window_s": 1.01, "steps": 2,
                           "kernel_s": {"ssd_scan_fwd": 0.01, "ssd_scan_bwd": 0.03, "flash_fwd_seg": 0.004,
                                        "flash_bwd_dkv_seg": 0.009}}
        record["_device_ops"] = ({"/device:TPU:0": ops}, (0.0, 1.01))
    return record


@pytest.mark.parametrize("name,value", [
    ("ssd_mamba2_time_pct", 17.0), ("ssd_conv_time_pct", 2.0), ("ssd_scan_time_pct", 5.0), ("ssd_latent_time_pct", 6.0),
    ("ssd_restarts_per_row", 3.6), ("swa_slots_held_pct", 100 / 64), ("swa_flash_full_time_pct", 1.3),
    ("swa_experts_time_pct", 2.0), ("swa_mfu_pct", 100 * 25e12 * 25 / (10 * 197e12)), ("swa_step_hbm_gb", 14.0),
])
def test_reader_on_a_hand_made_run(name, value):
    assert run.reader("per_layer", name)(_record(COUNTED, OPS)) == pytest.approx(value, rel=1e-6)


def test_rooflines_on_a_hand_made_run():
    record = _record(COUNTED, OPS)
    cfg, peak = record["config"], record["peak"]
    # the scans: 1.65 GB a step at 819 GB/s is 2.0 ms (their 0.2 TFLOP 1.1 ms); the two kernels took 0.04 s over two steps
    got = run.reader("per_layer", "ssd_scan_roofline_pct")(record)
    assert got == pytest.approx(100 * ssd_ops.step_bytes(cfg, 1, 8192) / 819e9 / 0.02) and 5 < got < 15
    # experts: 1/64 of a step's 8192 x 22 x 5 slots; the grouped product's kernels 0.02 s over two steps
    assert _ssd.slots_held_per_step(record) == pytest.approx(8192 * 22 * 5 / 64)
    least = max(flops_ssd_lm.expert_flops(cfg, 14080) / peak["bf16_flops_per_s"],
                flops_ssd_lm.expert_bytes(cfg, 14080) / peak["hbm_bytes_per_s"])
    got = run.reader("per_layer", "ssd_experts_roofline_pct")(record)
    assert got == pytest.approx(100 * least / 0.01) and got < 100


@pytest.mark.parametrize("name", [
    "ssd_mamba2_time_pct", "ssd_conv_time_pct", "ssd_scan_time_pct", "ssd_scan_roofline_pct", "ssd_latent_time_pct",
    "ssd_experts_roofline_pct", "ssd_restarts_per_row",
])
def test_reader_finds_nothing_in_a_program_without_the_scopes_and_counters(name):
    """The parent of the PR that brought them: no counter, no trace (untraced run), or a trace whose
    operations carry no such scope and whose kernels are another family's."""
    assert run.reader("per_layer", name)(_record()) is None
    unscoped = _record({}, [("jit(tos_train_step)/tos.loss_and_grad/jvp()/dot_general", 0.0, 0.5), (OLD_CONV, 0.5, 0.6)])
    unscoped["trace"]["kernel_s"] = {"ssm_scan_fwd": 0.1}
    assert run.reader("per_layer", name)(unscoped) is None
    # another family's cell, whose configuration has no hybrid_override_pattern: nothing to set the kernels against
    other = _record(COUNTED, OPS)
    other["config"] = {"hidden_size": 8}
    if "roofline" in name:
        assert run.reader("per_layer", name)(other) is None
    assert _ssd.kernel_seconds(unscoped) is None


def test_readers_on_a_recorded_trace():
    """The operations of one traced run of the cell on a v5e, by name (``data/ssd_lm_v5e_ops.json``: the longest
    and every one under a scope these readers look for), laid end to end: the readers find the scopes and the
    kernels under the names the chip gave them, and read what they read there."""
    with open(os.path.join(DATA, "ssd_lm_v5e_ops.json")) as f:
        recorded = json.load(f)
    ops, at = [], 0.0
    for name, seconds in recorded["ops"]:
        ops.append((name, at, at + seconds))
        at += seconds
    record = _record(COUNTED)
    record["trace"] = {"busy_s": recorded["busy_s"], "window_s": recorded["window_s"], "steps": recorded["steps"],
                       "kernel_s": recorded["kernel_s"]}
    record["_device_ops"] = ({"/device:TPU:0": ops}, (0.0, at))
    names = [name for name, _ in recorded["ops"]]
    assert any("jvp(Decoder)/layer_0/mamba2/tos.mamba2/tos.ssd_scan/" in name and "/ssd_scan_fwd/" in name for name in names)
    assert any("transpose(jvp(" in name and "tos.ssd_scan/ssd_scan_bwd/" in name for name in names)
    assert any("tos.mamba2/tos.ssm_conv" in name for name in names) and any("tos.moe_latent/latent_up" in name for name in names)
    on_chip = recorded["read_on_the_chip"]
    for name in ("ssd_mamba2_time_pct", "ssd_conv_time_pct", "ssd_scan_time_pct", "ssd_latent_time_pct"):
        # the fixture keeps every operation under these scopes: the share is the chip's
        assert run.reader("per_layer", name)(record) == pytest.approx(on_chip[name], rel=2e-3), name
    assert run.reader("per_layer", "ssd_scan_roofline_pct")(record) == pytest.approx(on_chip["ssd_scan_roofline_pct"], rel=1e-6)
    assert 0 < on_chip["ssd_scan_roofline_pct"] < 100 and 0 < on_chip["ssd_experts_roofline_pct"] < 100
    assert run.reader("per_layer", "ssd_conv_time_pct")(record) < run.reader("per_layer", "ssd_mamba2_time_pct")(record)
