"""Query-key pairs the window rule shows over the pairs of the blocks the window
kernels compute, for the rows the text plane emitted in the window (counters
``flash_win_pairs_visible_total`` / ``flash_win_pairs_in_blocks_total``): at 512 x 512
blocks a window of 512 fills about half of what it computes."""

from benchmarks.layer_metrics import _swa


def read(run):
    return _swa.counter_share_pct(run, "flash_win_pairs_visible_total", "flash_win_pairs_in_blocks_total")
