"""Device time under ``tos.cross_attn`` (a cross-attention layer: its query and output
projections, the causal segmented kernels on the keys and values another layer handed on,
the differential form; forward, recomputed and backward) over device busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.scope_pct(run, "tos.cross_attn")
