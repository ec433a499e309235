"""Device time of the traced steps spent in the backward pass (with the optimizer's update fused into it), over device busy time."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.phase_pct(run, "bwd")
