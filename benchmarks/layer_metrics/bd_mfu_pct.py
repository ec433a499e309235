"""The whole step's share of the chip's bf16 peak over the measured window: needed
operations (``flops_bd_lm.py``: forward + backward, no recomputation; the products
on both halves of a row, the last layer's clean half by its k and v alone, the head
on the noised half, attention by the visible pairs of the consumed rows, routed
experts by the slots that reached the experts held here) x rate / bf16 peak."""

from benchmarks.layer_metrics import _shared


def read(run):
    return _shared.mfu_pct(run)
