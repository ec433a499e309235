"""Seconds JAX spent loading executables from the persistent compile cache,
as it reports them (``cache_retrieval_time_sec``); 0 in a run that compiled."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.gauge(run, "compile_cache_load_seconds")
