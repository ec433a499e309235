"""Share of the causal triangle's attention blocks that the segmented flash
kernels compute for the rows the text plane emitted in the window (counters
``flash_blocks_needed_total`` / ``flash_blocks_dense_total``, as
``lm_flash_blocks_needed_pct``). 100 is the dense grid."""

from benchmarks.layer_metrics import _program


def read(run):
    needed = _program.counter(run, "flash_blocks_needed_total")
    dense = _program.counter(run, "flash_blocks_dense_total")
    if needed is None or not dense:
        return None
    return 100.0 * needed / dense
