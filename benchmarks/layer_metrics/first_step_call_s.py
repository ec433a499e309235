"""Host seconds inside the first call of the compiled train step (span
``step_dispatch`` of call 1): its trace, lowering, cache key, load or
compilation, and the dispatch. Less the ``step_*_s`` stages it is pjit's
own Python."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.gauge(run, "train_step_first_call_seconds")
