"""Share of the causal triangle's attention blocks that the segmented flash
kernels compute: blocks in which some query shares a document with some key,
over all blocks at or below the diagonal, for the rows the text plane emitted
in the window (counters ``flash_blocks_needed_total`` /
``flash_blocks_dense_total``, fed on the host where the packed batch is still
numpy, at the kernels' own block sizes). 100 is the dense grid."""

from benchmarks.layer_metrics import _program


def read(run):
    needed = _program.counter(run, "flash_blocks_needed_total")
    dense = _program.counter(run, "flash_blocks_dense_total")
    if needed is None or not dense:
        return None
    return 100.0 * needed / dense
