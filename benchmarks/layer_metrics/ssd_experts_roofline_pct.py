"""The grouped products' share of their roofline where an expert is two matrices
in a 1024-wide latent (1024 -> 2688 -> 1024, 8 held), by the slots that reached
the experts held here, over the ``ragged-dot-*`` kernels' device time per step."""

from benchmarks.layer_metrics import _ssd


def read(run):
    return _ssd.experts_roofline_pct(run)
