"""Seconds JAX spent tracing, all programs of the process up to the window's
end, a trace inside another counted once."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.gauge(run, "compile_trace_seconds")
