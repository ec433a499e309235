"""The window kernels' share of their roofline in the windowed differential layer: the larger
of needed operations / bf16 peak (the pairs the window rule shows in the traced rows, 40 maps
a position, scores at 64 and values at 128) and the operands' bytes / HBM bandwidth, over
the two kernels' device time in the traced steps."""

from benchmarks.layer_metrics import _ssm


def read(run):
    return _ssm.flash_roofline_pct(run, "window")
