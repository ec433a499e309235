"""Share of the segmented flash kernels' grid steps that compute a block:
needed blocks over grid steps, for the rows the text plane emitted in the
window (counters ``flash_blocks_needed_total`` / ``flash_grid_steps_total``,
fed on the host where the packed batch is still numpy, by the rule that
builds the kernels' work lists). The kernels' accumulating grid axis is as
long as the batch's longest list of needed blocks, so a row that needs fewer
parks for the rest: 100 is a batch whose rows all need as many blocks (one
row a batch reads 100). A program whose grid is not a work list (the parent
of the PR that brought this) has no such counter, and the reader returns
None."""

from benchmarks.layer_metrics import _program


def read(run):
    needed = _program.counter(run, "flash_blocks_needed_total")
    steps = _program.counter(run, "flash_grid_steps_total")
    if needed is None or not steps:
        return None
    return 100.0 * needed / steps
