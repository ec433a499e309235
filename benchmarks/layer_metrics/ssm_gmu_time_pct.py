"""Device time under ``tos.gmu`` (a gated memory unit: its two products and the gate by the
memory an earlier layer handed on; forward, recomputed and backward) over device busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.scope_pct(run, "tos.gmu")
