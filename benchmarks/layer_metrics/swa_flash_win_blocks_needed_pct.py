"""Attention blocks the window kernels compute over the causal triangle of the row,
for the rows the text plane emitted in the window (counters
``flash_win_blocks_needed_total`` / ``flash_win_blocks_dense_total``, fed on the host by
the rule that builds the kernels' work lists). 100 is a kernel that walked the
triangle; the full layers' share is what ``flash_blocks_needed_total`` gives."""

from benchmarks.layer_metrics import _swa


def read(run):
    return _swa.counter_share_pct(run, "flash_win_blocks_needed_total", "flash_win_blocks_dense_total")
