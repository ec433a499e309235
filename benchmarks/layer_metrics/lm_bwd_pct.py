"""Device time of the traced steps spent in the backward pass, over device
busy time. It holds the optimizer's update: XLA fuses it into the fusions
that produce the weight gradients, and a fusion is booked under its root."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.phase_pct(run, "bwd")
