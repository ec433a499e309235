"""Share of the block-diffusion flash kernels' grid steps that compute a block:
needed blocks over grid steps, for the rows the text plane emitted in the window
(counters ``flash_blocks_needed_total`` / ``flash_grid_steps_total``). The grid's
accumulating axis is as long as the batch's longest list of needed blocks, and a
row that needs fewer parks for the rest."""

from benchmarks.layer_metrics import _program


def read(run):
    needed = _program.counter(run, "flash_blocks_needed_total")
    steps = _program.counter(run, "flash_grid_steps_total")
    return None if needed is None or not steps else 100.0 * needed / steps
