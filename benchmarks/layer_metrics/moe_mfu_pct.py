"""Model FLOP/s utilization over the measured window: needed operations
(flops_moe_lm.py: forward + backward, no recomputation, routed experts by the
slots that reached the experts held here) x rate / bf16 peak."""

from benchmarks.layer_metrics import _shared


def read(run):
    return _shared.mfu_pct(run)
