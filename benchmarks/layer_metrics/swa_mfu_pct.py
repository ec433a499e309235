"""The whole step's share of the chip's bf16 peak over the measured window: needed
operations (``flops_swa_lm.py``: forward + backward, no recomputation; every layer's
products at its own head count, attention by the visible pairs of the consumed rows
under each layer's rule, the whole document in a full layer and the window in a sliding
one, routed experts by the slots that reached the experts held here) x rate / bf16 peak."""

from benchmarks.layer_metrics import _shared


def read(run):
    return _shared.mfu_pct(run)
