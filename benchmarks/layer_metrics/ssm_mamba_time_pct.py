"""Device time under ``tos.mamba`` (a state-space sub-layer: the in projection, the short
convolution, the low-rank step and state projections, the selective scan's two kernels and what
XLA writes for them, the gate and the out projection; forward, recomputed and backward)
over device busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.scope_pct(run, "tos.mamba")
