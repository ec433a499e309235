"""Device time under ``tos.mla`` (latent attention: its projections, rotary
and the flash kernels; forward, recomputed and backward) over device busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.scope_pct(run, "tos.mla")
