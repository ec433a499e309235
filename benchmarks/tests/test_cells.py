"""BENCHMARK.json against the contract's shape rules and the files it names."""

import json
import os
import re

import pytest

from benchmarks import run

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_and_units(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [entry["name"] for entry in bench[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    for cell in bench["workloads"]:
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for metric in bench["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert metric["source"] in ("host_clock", "device_trace") and 0 < metric["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    assert sum(c["chips"] == 4 for c in bench["workloads"]) <= max(1, len(bench["workloads"]) // 4)


def test_every_cell_resolves_to_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for cell in bench["workloads"]:
        _, resolved, config, traffic = run.resolve(cell["name"])
        assert resolved["chips"] == cell["chips"]
        used.add(cell["config"])
        assert configs[cell["config"]]["file"] == "benchmarks/configs/{}.json".format(cell["config"])
        here = os.path.join(ROOT, "benchmarks")
        assert os.path.exists(os.path.join(here, "families", config["family"] + ".py"))
        assert os.path.exists(os.path.join(here, "reference", config["family"] + ".py"))
        assert os.path.exists(os.path.join(here, "limits", cell["name"] + ".json"))
        assert traffic["check_steps"] >= 2
    assert used == set(configs)


def test_every_metric_has_a_reader_and_a_target(bench):
    cells = {c["name"] for c in bench["workloads"]}
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    for metric in bench["end_to_end"]:
        assert callable(run.reader("end_to_end", metric["name"]))
        assert set(metric.get("workloads", cells)) <= cells
    for metric in bench["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert callable(run.reader("per_layer", metric["name"]))
        target = end_to_end[metric["moves"]]
        reported_in = set(target.get("workloads", cells))
        assert set(metric.get("workloads", reported_in)) <= reported_in, metric["name"]
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in bench["end_to_end"] if m["name"] != "setup_s")
        assert any(cell in m.get("workloads", cells) for m in bench["per_layer"])


def test_peaks_name_their_source():
    peaks = run._load("peaks.json")
    assert "cloud.google.com" in peaks["source"]
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_workloads_lists_decide_which_metrics_a_cell_reports(bench, monkeypatch):
    monkeypatch.setattr(run, "reader", lambda kind, name: lambda record: 1.0)

    def names(workload, kind):
        return list(run.metrics_of(bench, kind, {"workload": workload}))

    lm = names("lm1024.packed4k", "per_layer")
    assert "lm_mfu_pct" in lm and "launch_s" in lm and not any(n.startswith("img_") for n in lm)
    # a probe with no entry reports what the cells of its configuration report
    probe = names("resnet50.jpeg", "per_layer")
    assert "img_mfu_pct" in probe and "launch_s" in probe and not any(n.startswith("lm_") for n in probe)
    assert names("resnet50.jpeg", "end_to_end") == ["images_per_s_per_chip", "setup_s"]
