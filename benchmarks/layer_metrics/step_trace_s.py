"""Seconds JAX spent tracing the train step (``jaxpr_trace_duration`` of
``tos_train_step``), inside the first call."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.gauge(run, "train_step_trace_seconds")
