"""The chunked scan kernels' share of their roofline: the larger of the chunk
products' operations / bf16 peak and the operands' and chunk states' bytes /
HBM bandwidth (``ssd_ops.py``), over ``ssd_scan_fwd`` + ``ssd_scan_bwd``'s
device time per step."""

from benchmarks.layer_metrics import _ssd


def read(run):
    return _ssd.scan_roofline_pct(run)
