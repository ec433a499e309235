"""tensorflowonspark_tpu — a TPU-native distributed DL framework with the
capabilities of TensorFlowOnSpark.

A Spark (or Spark-like) application turns its executors into a distributed
deep-learning cluster: the driver reserves TPU hosts, each executor bootstraps a
jax process that joins a global device mesh (ICI within a slice, DCN across
slices via ``jax.distributed``), Spark RDD/DataFrame partitions stream into the
TPU hosts through a local IPC feed plane, and training/inference is expressed as
pjit-compiled SPMD programs over ``jax.sharding.Mesh`` axes (dp/fsdp/tp/sp/ep).

Public module layout intentionally mirrors the reference
(``/root/reference/tensorflowonspark``) so users of TensorFlowOnSpark can switch
with minimal changes, while every implementation is TPU-first:

* :mod:`~tensorflowonspark_tpu.TFCluster` — driver-side cluster lifecycle API.
* :mod:`~tensorflowonspark_tpu.TFSparkNode` — executor-side node runtime.
* :mod:`~tensorflowonspark_tpu.TFNode` — in-``main_fun`` helper API (DataFeed).
* :mod:`~tensorflowonspark_tpu.TFManager` — per-executor IPC manager.
* :mod:`~tensorflowonspark_tpu.reservation` — driver-hosted control plane.
* :mod:`~tensorflowonspark_tpu.tpu_info` — TPU topology discovery (gpu_info analogue).
* :mod:`~tensorflowonspark_tpu.pipeline` — ML-pipeline Estimator/Model layer.
* :mod:`~tensorflowonspark_tpu.dfutil` — TFRecord <-> DataFrame utilities.
* :mod:`~tensorflowonspark_tpu.parallel` — mesh / sharding / collectives / ring attention.
* :mod:`~tensorflowonspark_tpu.train` — pjit training strategies + checkpointing.
* :mod:`~tensorflowonspark_tpu.models` — flax model zoo (mnist, resnet, segmentation, transformer).
* :mod:`~tensorflowonspark_tpu.backends` — Spark and local multi-process execution backends.

Importing this package configures NO logging: applications opt in with
:func:`tensorflowonspark_tpu.util.setup_logging` (examples call it; the jax child
process calls it on entry). The format carries
process/thread like the reference (/root/reference/tensorflowonspark/__init__.py:3)
because the runtime spans a driver, N executor processes and N jax child
processes.
"""

__version__ = "0.1.0"
