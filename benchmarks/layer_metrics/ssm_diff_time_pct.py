"""Device time under ``tos.diff_attn`` (differential attention outside the kernels: lambda,
the subtraction of a pair's two maps' outputs and the sub-norm, in windowed, full and cross
layers alike; forward, recomputed and backward) over device busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.scope_pct(run, "tos.diff_attn")
