"""``lm_flash_blocks_needed_pct`` on a recorded counters dict: needed over
dense of the window's deltas; nothing where the program does not count."""

import json
import os

import pytest

from benchmarks import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
#: the counters line of a rehearsed window (``bench.counters``), cut to the
#: two this reader takes and two it must leave alone
RECORDED = {
    "flash_blocks_needed_total": 1312.0, "flash_blocks_dense_total": 2160.0,
    "text_tokens_packed_total": 245367.0, "train_steps_dispatched_total": 15.0,
}


def _run(counters):
    return {"workload": "lm1024.packed4k", "window": {"seconds": 10.0, "counters": counters, "gauges": {}},
            "trace": None}


@pytest.mark.parametrize("counters,value", [
    (RECORDED, 100.0 * 1312 / 2160),
    ({"flash_blocks_needed_total": 36.0, "flash_blocks_dense_total": 36.0}, 100.0),
    # the parent: the program feeds no such counter
    ({k: v for k, v in RECORDED.items() if not k.startswith("flash_")}, None),
    ({}, None),
    # a window in which the text plane emitted nothing
    ({"flash_blocks_needed_total": 0.0, "flash_blocks_dense_total": 0.0}, None),
])
def test_needed_over_dense(counters, value):
    got = bench_run.reader("per_layer", "lm_flash_blocks_needed_pct")(_run(counters))
    assert got is None if value is None else got == pytest.approx(value)


def test_declared_for_the_packed_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == "lm_flash_blocks_needed_pct")
    assert entry == {
        "name": "lm_flash_blocks_needed_pct", "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "kernels", "moves": "tokens_per_s_per_chip", "workloads": ["lm1024.packed4k"]}
