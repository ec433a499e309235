"""Seconds a loaded train step spent beside its load: its
``backend_compile_duration`` less its ``cache_retrieval_time_sec``, i.e. the
cache's key (the module canonicalised, serialised and hashed); 0 in a run
that compiled the step."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.gauge(run, "train_step_cache_lookup_seconds")
