"""Positions at which the chunked scans (and the convolutions) start anew, a row
the text plane emitted in the window: ``ssm_scan_restarts_total`` over the rows
(``ssm_scan_positions_total`` / ``seq_len``)."""

from benchmarks.layer_metrics import _ssm


def read(run):
    return _ssm.restarts_per_row(run)
