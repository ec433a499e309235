"""The full layers' flash kernels' share of their roofline: the larger of needed
operations / bf16 peak (the pairs the causal rule shows within each document of the
consumed rows, at 48 query heads x 128) and q, o, do, dq at the 48 query heads plus k, v,
dk, dv at the 8 key/value heads / HBM bandwidth, over the two kernels' device time per
step."""

from benchmarks.layer_metrics import _swa


def read(run):
    return _swa.flash_roofline_pct(run, False)
