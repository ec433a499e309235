"""Device time under ``tos.ssm_conv`` inside ``tos.mamba2`` (the short causal
convolution over ``x``, ``B`` and ``C``, its restarts and its SiLU) over device
busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.share_pct(run, lambda name: _moe.in_scope(name, "tos.mamba2") and _moe.in_scope(name, "tos.ssm_conv"))
