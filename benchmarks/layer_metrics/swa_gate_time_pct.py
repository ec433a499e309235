"""Device time under ``tos.attn_gate`` (the gate's projection, its sigmoid and the
product with the heads' outputs, in full and sliding layers alike; forward,
recomputed and backward) over device busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.scope_pct(run, "tos.attn_gate")
