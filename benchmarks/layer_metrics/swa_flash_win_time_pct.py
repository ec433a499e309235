"""Device time of the two window kernels (``flash_fwd_win``, ``flash_bwd_dkv_win``:
72 query heads over 8 key/value heads of 128, window 512) over device busy time."""

from benchmarks.layer_metrics import _swa


def read(run):
    return _swa.flash_time_pct(run, True)
