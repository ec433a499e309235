"""Device time under ``tos.gqa`` (a full layer's attention: projections at 48
query heads, YaRN's rotary on half the head, the gate, the causal segmented
kernels and the output projection; forward, recomputed and backward) over
device busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.scope_pct(run, "tos.gqa")
