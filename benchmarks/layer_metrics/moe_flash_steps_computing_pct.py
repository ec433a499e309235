"""Share of the segmented flash kernels' grid steps that compute a block, for
the rows the text plane emitted in the window (counters
``flash_blocks_needed_total`` / ``flash_grid_steps_total``:
``lm_flash_steps_computing_pct``'s reader, under this cell's name). One row a
batch reads 100: the grid is as long as that row's list. None where the
program has no such counter."""

from benchmarks.layer_metrics.lm_flash_steps_computing_pct import read  # noqa: F401
