"""Device time under ``tos.mamba2`` (a Mamba-2 block's mixer: the in
projection, the convolution, the chunked scan, the gated group norm and the out
projection; forward, recomputed and backward) over device busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.scope_pct(run, "tos.mamba2")
