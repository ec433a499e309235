"""``moe_mhc_roofline_pct`` and its bytes function on a planted trace and by
hand, as ``test_moe_lm.py`` holds the other ``moe_`` readers."""

import pytest

from benchmarks import mhc_bytes, run
from benchmarks.tests.test_moe_lm import COUNTED, MHC, OPS, _record

BWD = "jit(tos_train_step)/tos.loss_and_grad/transpose(jvp(Decoder))/layer_1/res_mlp/tos.mhc/tos.mhc/mhc_read_bwd/pallas_call"
REDONE = "jit(tos_train_step)/tos.loss_and_grad/transpose(jvp(Decoder))/rematted_computation/layer_1/tos.mhc/mhc_merge/pallas_call"


def test_bytes_by_hand():
    record = _record()
    cfg = record["config"]
    streams, one = mhc_bytes.stream_bytes(cfg, 1, 8192)
    assert (streams, one) == (8192 * 4 * 3584 * 2, 8192 * 3584 * 2)  # 234.9 MB and 58.7 MB
    forward, backward = mhc_bytes.sublayer_bytes(cfg, 1, 8192, remat=False)
    assert forward == 3 * streams + 2 * one and backward == 6 * streams + 3 * one
    assert mhc_bytes.sublayer_bytes(cfg, 1, 8192, remat=True) == (2 * forward, backward)
    # ten sub-layers of 12 S + 7 H: 32.3 GB a step, 39.4 ms at 819 GB/s
    step = mhc_bytes.step_bytes(cfg, 1, 8192, remat=True)
    assert step == 10 * (12 * streams + 7 * one)
    assert step / record["peak"]["hbm_bytes_per_s"] == pytest.approx(39.43e-3, rel=1e-3)
    assert mhc_bytes.step_bytes(cfg, 2, 8192, remat=False) == 2 * 10 * (9 * streams + 5 * one)


def test_reader_on_a_hand_made_run():
    # the planted operations under tos.mhc: 0.15 s inside the traced window of two steps (a third lies outside it)
    record = _record(COUNTED, {}, OPS)
    least = mhc_bytes.step_bytes(record["config"], 1, 8192, True) / 819e9
    got = run.reader("per_layer", "moe_mhc_roofline_pct")(record)
    assert got == pytest.approx(100 * least / 0.075) and got < 100
    # forward, recomputed and backward operations of the kernels count alike, by the scope
    kernels = _record(COUNTED, {}, [(MHC, 0.0, 0.05), (BWD, 0.1, 0.2), (REDONE, 0.3, 0.35)])
    assert run.reader("per_layer", "moe_mhc_roofline_pct")(kernels) == pytest.approx(100 * least / 0.1)


def test_reader_finds_nothing_without_the_scope_or_the_streams():
    assert run.reader("per_layer", "moe_mhc_roofline_pct")(_record()) is None  # an untraced run
    unscoped = _record({}, {}, [("jit(tos_train_step)/tos.loss_and_grad/jvp()/dot_general", 0.0, 0.5)])
    assert run.reader("per_layer", "moe_mhc_roofline_pct")(unscoped) is None
    one_stream = _record(COUNTED, {}, OPS)
    one_stream["config"] = {k: v for k, v in one_stream["config"].items() if k != "hc_mult"}
    assert run.reader("per_layer", "moe_mhc_roofline_pct")(one_stream) is None
