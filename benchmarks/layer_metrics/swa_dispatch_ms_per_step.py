"""Host time inside one call of the compiled step (span ``step_dispatch``)."""

from benchmarks.layer_metrics import _program


def read(run):
    seconds = _program.counter(run, "train_step_dispatch_seconds_total")
    steps = _program.counter(run, "train_steps_dispatched_total")
    return None if seconds is None or not steps else 1000.0 * seconds / steps
