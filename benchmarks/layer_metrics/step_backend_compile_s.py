"""Seconds the backend spent compiling the train step; 0 on a warm start."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.gauge(run, "train_step_backend_compile_seconds")
