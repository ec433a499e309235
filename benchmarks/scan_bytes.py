"""Bytes the selective scans of the ``ssm_lm`` family's step must move
between HBM and the chip, whatever implements them, from shapes alone.

A Mamba layer's scan reads, a position, the step before its softplus and the
convolved input ``c`` (``D`` channels each, in the configuration's ``dtype``)
and ``B`` and ``C`` (``S`` states each), and writes ``y`` (``D``): forward ``3
D + 2 S`` values. The backward reads those four again with ``dy`` (``D``) and
writes the cotangents of the step and of ``c`` (``D`` each) and of ``B`` and
``C``: ``5 D + 4 S``. ``A``, the skip and their gradients are a few hundred
kilobytes a layer and are not counted, nor is anything an implementation
keeps for itself (boundary states, widened operands). A step that recomputes
its layers (``remat``) does not need the forward twice: the scan's results
are what a recomputed layer keeps.

Bytes only: the recurrence is ``D x S`` multiply-adds and one exponential a
position on the vector unit, for which ``peaks.json`` has no peak, so a share
of this roofline reads low by construction (PERF.md section 7).
"""

from benchmarks import flops_ssm_lm

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_bytes(cfg, rows, seq_len):
    """``(forward, backward)`` bytes of one Mamba layer's scan."""
    inner, states, _ = flops_ssm_lm.sizes(cfg)
    one = rows * seq_len * _ITEMSIZE[cfg["dtype"]]
    return one * (3 * inner + 2 * states), one * (5 * inner + 4 * states)


def step_bytes(cfg, rows, seq_len):
    """Bytes of a whole step's scans on one chip."""
    layers = flops_ssm_lm.layer_kinds(cfg).count("mamba")
    return layers * sum(layer_bytes(cfg, rows, seq_len))
