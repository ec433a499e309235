"""trace_reduce.py on a small recorded trace and on hand-made intervals."""

import os

import pytest

from benchmarks import trace_reduce

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "cpu_matmul.xplane.pb")


def test_interval_arithmetic():
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert trace_reduce.total([(0, 2), (5, 6)]) == 3


def test_recorded_cpu_trace():
    # three jitted 512 x 512 matmuls, each under bench.dispatch then bench.fence
    trace = trace_reduce.load(RECORDED)
    assert [name for name, _, _ in trace["host"]] == ["bench.dispatch", "bench.fence"] * 3
    out = trace_reduce.reduce_trace(trace)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["device_ops"][0][0].startswith("dot_general")
    assert out["idle_gaps"] and all(label.startswith("bench.") for label, _ in out["idle_gaps"])


def test_two_chip_trace_by_hand():
    hlo = '%custom-call.7 = bf16[64,4096,64] custom-call(), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/attn/flash_fwd_seg/pallas_call"}'
    ops = [("%fusion.1 = f32[4] fusion(%p), kind=kLoop", 0.0, 4.0), ("%custom-call.7 = bf16[64,4096,64] custom-call()", 4.0, 6.0),
           ("%all-reduce.3 = f32[8] all-reduce(%g)", 6.0, 7.0), ("fusion.2", 8.0, 9.0)]
    trace = {
        "devices": {"/device:TPU:0": ops, "/device:TPU:1": ops},
        "host": [("bench.next_batch", 0.0, 1.0), ("bench.dispatch", 1.0, 7.2), ("bench.fence", 7.2, 10.0)],
    }
    out = trace_reduce.reduce_trace(trace, hlo_text=hlo)
    assert out["window_s"] == 10.0 and out["busy_s"] == 8.0 and out["devices"] == 2
    assert out["kernel_s"] == {"flash_fwd_seg": 2.0}
    assert out["collective_s"] == 1.0 and out["collective_exposed_s"] == 1.0
    assert out["device_ops"][0] == ["fusion.1_x2", 4.0]
    assert out["idle_gaps"] == [["bench.fence", 1.0]] * 4
    # an asynchronous all-reduce from 3.0 to 8.5 runs beside the operations: it
    # is exposed only where none of them computes (6.0 to 8.0)
    trace["async"] = {plane: [("%all-reduce-start.1 = f32[8] all-reduce-start(%p)", 3.0, 8.5)]
                      for plane in trace["devices"]}
    out = trace_reduce.reduce_trace(trace, hlo_text=hlo)
    assert out["busy_s"] == 8.0 and out["collective_s"] == 5.5 and out["collective_exposed_s"] == 2.0


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace({"devices": {}, "host": []})
