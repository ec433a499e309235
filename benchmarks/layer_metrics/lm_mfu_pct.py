"""Model FLOP/s utilization over the measured window: needed operations
(flops.py; forward + backward, no recomputation) x rate / bf16 peak."""

from benchmarks.layer_metrics import _shared


def read(run):
    return _shared.mfu_pct(run)
