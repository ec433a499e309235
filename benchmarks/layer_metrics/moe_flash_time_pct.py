"""Device time of the three segmented flash kernels (at latent attention's
widths: keys 192, values 128) over device busy time."""

from benchmarks.layer_metrics import _shared


def read(run):
    seconds = _shared.flash_seconds(run)
    return None if seconds is None else 100.0 * seconds / run["trace"]["busy_s"]
