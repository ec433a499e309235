"""The readers of a start's compile work (PR 36): each on a hand-made ``run``
record, as ``child.py`` hands the program's gauges over, and on a program
that does not keep them (the parent)."""

import pytest

from benchmarks import run as bench_run

GAUGED = {
    "train_step_traces": 1.0, "train_step_trace_seconds": 4.5, "train_step_lower_seconds": 3.25,
    "train_step_cache_lookup_seconds": 2.0, "train_step_cache_load_seconds": 6.5,
    "train_step_backend_compile_seconds": 0.0, "train_step_first_call_seconds": 16.5,
    "compile_trace_seconds": 7.75, "compile_lower_seconds": 5.5, "compile_cache_lookup_seconds": 2.5,
    "compile_cache_load_seconds": 11.0, "data_first_batch_seconds": 0.75,
}
READERS = [
    ("step_traces", 1.0), ("step_trace_s", 4.5), ("step_lower_s", 3.25), ("step_cache_lookup_s", 2.0),
    ("step_cache_load_s", 6.5), ("step_backend_compile_s", 0.0), ("first_step_call_s", 16.5),
    ("first_step_rest_s", 21.75 - 16.5), ("setup_trace_s", 7.75), ("setup_lower_s", 5.5),
    ("setup_cache_lookup_s", 2.5), ("first_batch_s", 0.75),
]


def _run(gauges=None, parts=None):
    return {"workload": "xing4-a4b.packed8k", "parts": {"first_step_s": 21.75} if parts is None else parts,
            "window": {"seconds": 10.0, "counters": {}, "gauges": gauges or {}}, "trace": None}


@pytest.mark.parametrize("name,value", READERS)
def test_compile_reader_on_a_hand_made_run(name, value):
    read = bench_run.reader("per_layer", name)
    assert read(_run(GAUGED)) == value
    # a program that keeps no such gauge (the parent): nothing to read, nothing raised
    assert read(_run()) is None
    # a window record without gauges at all (a program older than PR 24)
    assert read({"parts": {"first_step_s": 21.75}, "window": {"seconds": 10.0, "counters": {}}}) is None


def test_the_rest_of_the_first_step_needs_both_its_sides():
    read = bench_run.reader("per_layer", "first_step_rest_s")
    assert read(_run(GAUGED, parts={})) is None
    assert read(_run({"train_step_traces": 1.0})) is None
    # not clamped: a first call longer than the harness's first step would be a fault to see
    assert read(_run(dict(GAUGED, train_step_first_call_seconds=22.0))) == -0.25


def test_every_cell_reports_the_twelve_and_the_stages_add_up():
    """``BENCHMARK.json`` lists the twelve without ``workloads`` (every cell
    reports ``setup_s``), and on the record above the step's stages fit in its
    first call, the first call in the first step."""
    bench, cell, _, _ = bench_run.resolve("resnet50.warm")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, _ in READERS:
        assert "workloads" not in entries[name] and entries[name]["moves"] == "setup_s"
    twelve = dict(bench, per_layer=[entries[name] for name, _ in READERS])
    out = bench_run.metrics_of(twelve, "per_layer", dict(_run(GAUGED), workload=cell["name"]))
    got = {name: out[name]["value"] for name, _ in READERS}
    assert got == dict(READERS)
    stages = sum(got[n] for n in ("step_trace_s", "step_lower_s", "step_cache_lookup_s", "step_cache_load_s",
                                  "step_backend_compile_s"))
    assert stages <= got["first_step_call_s"] <= 21.75 and got["step_cache_load_s"] <= GAUGED["compile_cache_load_seconds"]
