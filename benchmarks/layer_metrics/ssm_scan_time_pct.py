"""Device time of the selective scan's two kernels (``ssm_scan_fwd``, ``ssm_scan_bwd``: 5120
channels x 16 states a position, float32 state in VMEM, restarting a document) over
device busy time."""

from benchmarks.layer_metrics import _ssm


def read(run):
    return _ssm.kernel_time_pct(run, _ssm.SCAN_KERNELS)
