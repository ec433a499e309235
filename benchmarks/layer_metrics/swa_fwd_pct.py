"""Device time of the traced steps spent in the forward pass and the loss, over device busy time."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.phase_pct(run, "fwd")
