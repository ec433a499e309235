"""Share of the window the loop's thread spent waiting on the input
pipeline's queue (span ``batch_wait``)."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.window_pct(run, "data_consumer_wait_seconds_total")
