"""Device busy time per step in the traced window (mean over chips)."""

from benchmarks.layer_metrics import _shared


def read(run):
    return _shared.step_device_ms(run)
