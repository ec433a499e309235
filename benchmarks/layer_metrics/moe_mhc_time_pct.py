"""Device time under ``tos.mhc`` (the hyper-connections: the maps' products,
Sinkhorn, reading and mixing the residual streams) over device busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.scope_pct(run, "tos.mhc")
