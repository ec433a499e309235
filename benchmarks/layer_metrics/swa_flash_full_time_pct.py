"""Device time of the full layers' two flash kernels (``flash_fwd_seg``,
``flash_bwd_dkv_seg``: 48 query heads over 8 key/value heads of 128) over device
busy time."""

from benchmarks.layer_metrics import _swa


def read(run):
    return _swa.flash_time_pct(run, False)
