"""Device time under ``tos.moe_latent`` (the routed experts' two latent
projections, down before the dispatch and up after the combine) over device
busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.scope_pct(run, "tos.moe_latent")
