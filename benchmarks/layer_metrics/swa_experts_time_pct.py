"""Device time under ``tos.moe_experts`` (the fences round the grouped products
and the SiLU gate between them) plus the grouped products' own kernels, which
XLA names ``ragged-dot-*`` outside every scope, over device busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.share_pct(run, lambda name: _moe.in_scope(name, "tos.moe_experts") or _moe.is_grouped_product(name))
