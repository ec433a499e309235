"""Seconds from the start of the input iterator the window reads from to its
first batch: producer start, first shard read, first pack or decode."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.gauge(run, "data_first_batch_seconds")
