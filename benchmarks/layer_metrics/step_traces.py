"""Programs built of the train step before the window's end (its lowerings, as
JAX reports them): 1, and one more for every call whose arguments' types,
shardings or layouts were new."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.gauge(run, "train_step_traces")
