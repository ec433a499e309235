"""Seconds loaded programs spent beside their loads (cache keys), all programs
of the process up to the window's end; ``cache_load_s`` is the loads."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.gauge(run, "compile_cache_lookup_seconds")
