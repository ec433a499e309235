"""Device time under ``tos.ssd_scan`` (the chunked scan: both rules of its
``custom_vjp``, kernels ``ssd_scan_fwd`` / ``ssd_scan_bwd``, and what XLA does
round them: the step times the input, the decays' running sums, the skip) over
device busy time."""

from benchmarks.layer_metrics import _moe


def read(run):
    return _moe.scope_pct(run, "tos.ssd_scan")
