"""Seconds JAX spent lowering the train step to a StableHLO module
(``jaxpr_to_mlir_module_duration`` of ``jit(tos_train_step)``)."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.gauge(run, "train_step_lower_seconds")
