"""Share of the window lost to stalls the program's step callable counted
(``train_step_stall_seconds_total``)."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.window_pct(run, "train_step_stall_seconds_total")
