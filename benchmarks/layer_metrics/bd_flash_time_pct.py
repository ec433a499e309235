"""Device time of the two block-diffusion flash kernels (``flash_fwd_bd``,
``flash_bwd_dkv_bd``: 32 query heads over 4 key/value heads of 128) over device
busy time."""

from benchmarks.layer_metrics import _bd


def read(run):
    seconds = _bd.flash_seconds(run)
    return None if seconds is None else 100.0 * seconds / run["trace"]["busy_s"]
