"""The compiled step's device memory by its own memory_analysis():
arguments + outputs + temporaries - aliased, in GB."""

from benchmarks.layer_metrics import _shared


def read(run):
    return _shared.step_hbm_gb(run)
