"""Share of the window lost to stalls the program's step callable counted:
dispatch intervals over twice the median of the last 32 that ended with the
step before still running, each by its excess over the median."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.window_pct(run, "train_step_stall_seconds_total")
