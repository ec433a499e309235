"""Device time under ``tos.gqa`` (grouped-query attention: its projections, the
head norms, rotary and the mask kernels; forward, recomputed and backward) over
device busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.scope_pct(run, "tos.gqa")
