"""The causal segmented kernels' share of their roofline in the full and the cross layer:
the larger of needed operations / bf16 peak (the pairs the causal rule shows within each
document of the traced rows, 40 maps a position and layer, scores at 64 and values at 128)
and the operands' bytes / HBM bandwidth, over the two kernels' device time in the traced steps."""

from benchmarks.layer_metrics import _ssm


def read(run):
    return _ssm.flash_roofline_pct(run, "full")
