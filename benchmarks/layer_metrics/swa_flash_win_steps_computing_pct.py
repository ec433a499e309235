"""Share of the window kernels' grid steps that compute a block: needed blocks over
grid steps, for the rows the text plane emitted in the window (counters
``flash_win_blocks_needed_total`` / ``flash_win_grid_steps_total``). The grid's
accumulating axis is as long as the batch's longest list of needed blocks, and a row
that needs fewer parks for the rest: 100 at one row a batch."""

from benchmarks.layer_metrics import _swa


def read(run):
    return _swa.counter_share_pct(run, "flash_win_blocks_needed_total", "flash_win_grid_steps_total")
