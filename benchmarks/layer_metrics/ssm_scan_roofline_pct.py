"""The scan kernels' share of their roofline, bytes only: the least time one chip could
take to move what a step's scans must move, whatever implements them (``scan_bytes.py``: the
step, ``c``, ``B`` and ``C`` in and ``y`` out, and the backward's twins) at the HBM's bandwidth,
over the two kernels' device time per step. The recurrence is bound by the vector unit,
for which ``peaks.json`` has no peak, so this reads low by construction."""

from benchmarks.layer_metrics import _ssm


def read(run):
    return _ssm.scan_roofline_pct(run)
