"""Device time under ``tos.moe_route`` (router, softmax, top-10 of 256, sorting the
slots, gathering their rows, weighting and combining) over device busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.scope_pct(run, "tos.moe_route")
