"""Seconds JAX spent lowering jaxprs to StableHLO modules, all programs of the
process up to the window's end."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.gauge(run, "compile_lower_seconds")
