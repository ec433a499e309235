"""The window kernels' share of their roofline: the least time one chip could take
for a step's attention in the sliding layers, whatever implements it, the larger of
needed operations / bf16 peak (the pairs the window rule shows in the consumed rows, at
72 query heads x 128) and q, o, do, dq at the 72 query heads plus k, v, dk, dv at the 8
key/value heads / HBM bandwidth, over the two kernels' device time per step."""

from benchmarks.layer_metrics import _swa


def read(run):
    return _swa.flash_roofline_pct(run, True)
