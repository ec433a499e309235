"""Device time under ``tos.swa`` (a sliding layer's attention: projections at 72
query heads, rotary, the gate, the window kernels and the output projection;
forward, recomputed and backward) over device busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.scope_pct(run, "tos.swa")
