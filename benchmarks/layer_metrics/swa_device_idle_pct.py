"""1 - union of device operation intervals / traced window (mean over chips)."""

from benchmarks.layer_metrics import _shared


def read(run):
    return _shared.device_idle_pct(run)
