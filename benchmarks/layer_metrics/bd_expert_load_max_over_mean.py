"""The fullest held expert's slots over the held experts' mean (mean over
the layers), at the last step booked in the window (gauge
``moe_expert_load_max_over_mean``): 1 is an even load."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.gauge(run, "moe_expert_load_max_over_mean")
