"""Attention blocks the block-diffusion flash kernels compute over the causal
triangle of the row as the model reads it (both copies: 8192 positions), for the
rows the text plane emitted in the window (counters ``flash_blocks_needed_total`` /
``flash_blocks_dense_total``, fed on the host by the rule that builds the kernels'
work lists). 100 is a kernel that walked the doubled row's triangle."""

from benchmarks.layer_metrics import _program


def read(run):
    needed = _program.counter(run, "flash_blocks_needed_total")
    dense = _program.counter(run, "flash_blocks_dense_total")
    return None if needed is None or not dense else 100.0 * needed / dense
