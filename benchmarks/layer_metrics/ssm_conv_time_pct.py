"""Device time under ``tos.ssm_conv`` (the state-space layers' short convolution, four taps a
channel with a document's fence, and its SiLU: XLA's fusions; forward, recomputed and
backward) over device busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.scope_pct(run, "tos.ssm_conv")
