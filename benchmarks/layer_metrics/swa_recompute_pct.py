"""Device time of the traced steps spent in blocks computed again for the backward pass, over device busy time."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.phase_pct(run, "recompute")
