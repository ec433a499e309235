"""Seconds loading the train step's executable from the persistent compile
cache (its share of ``cache_load_s``); 0 in a run that compiled it."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.gauge(run, "train_step_cache_load_seconds")
