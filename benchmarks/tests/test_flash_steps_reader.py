"""``lm_`` / ``moe_flash_steps_computing_pct`` on a recorded counters dict:
needed blocks over grid steps of the window's deltas; nothing where the
program does not count its grid steps (the parent)."""

import json
import os

import pytest

from benchmarks import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
#: the counters line of a window on the chip (``bench.counters``, 20 steps of
#: ``lm1024.packed4k``), cut to the two these readers take and what they must
#: leave alone
RECORDED = {
    "flash_blocks_needed_total": 1728.0, "flash_blocks_dense_total": 2880.0, "flash_grid_steps_total": 2324.0,
    "text_tokens_packed_total": 327180.0, "train_steps_dispatched_total": 20.0,
}
CELLS = {"lm": "lm1024.packed4k", "moe": "xing4-a4b.packed8k"}


def _run(prefix, counters):
    return {"workload": CELLS[prefix], "window": {"seconds": 10.0, "counters": counters, "gauges": {}}, "trace": None}


@pytest.mark.parametrize("prefix", sorted(CELLS))
@pytest.mark.parametrize("counters,value", [
    (RECORDED, 100.0 * 1728 / 2324),
    # one row a batch: the grid is that row's list
    ({"flash_blocks_needed_total": 820.0, "flash_grid_steps_total": 820.0}, 100.0),
    # the parent: needed and dense are counted, grid steps are not
    ({k: v for k, v in RECORDED.items() if k != "flash_grid_steps_total"}, None),
    ({}, None),
    # a window in which the text plane emitted nothing
    ({"flash_blocks_needed_total": 0.0, "flash_grid_steps_total": 0.0}, None),
], ids=["recorded", "one_row", "parent", "no_counters", "nothing_emitted"])
def test_needed_over_grid_steps(prefix, counters, value):
    got = bench_run.reader("per_layer", prefix + "_flash_steps_computing_pct")(_run(prefix, counters))
    assert got is None if value is None else got == pytest.approx(value)


@pytest.mark.parametrize("prefix", sorted(CELLS))
def test_declared_for_the_cells_that_report_blocks_needed(prefix):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    entry = by_name[prefix + "_flash_steps_computing_pct"]
    assert entry == {
        "name": prefix + "_flash_steps_computing_pct", "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "kernels", "moves": "tokens_per_s_per_chip", "workloads": [CELLS[prefix]]}
    assert entry["workloads"] == by_name[prefix + "_flash_blocks_needed_pct"]["workloads"]
