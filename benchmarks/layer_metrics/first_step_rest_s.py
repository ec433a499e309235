"""``first_step_s`` less the first call of the step: the first run to the
fence, plus the first batch where the family had not drawn it yet. Nothing
without the program's gauge (the parent)."""

from benchmarks.layer_metrics import _program


def read(run):
    whole = run["parts"].get("first_step_s")
    call = _program.gauge(run, "train_step_first_call_seconds")
    return None if whole is None or call is None else whole - call
