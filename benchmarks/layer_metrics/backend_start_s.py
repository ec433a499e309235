"""The jax child joining the world and starting the backend (span
``child_backend_start``, before ``main_fun``): the accelerator runtime's start."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.gauge(run, "node_backend_start_seconds")
