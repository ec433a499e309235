"""Parallelism core: device meshes, shardings, collectives, ring attention.

This package is the TPU-native replacement for the reference's entire
"training plane" (SURVEY.md §2.8): where TensorFlowOnSpark delegated
distribution to TF's gRPC ClusterSpec + NCCL/RING collective all-reduce
(/root/reference/tensorflowonspark/TFNode.py:123-129, TFSparkNode.py:277-299),
here distribution is expressed as shardings over a named
:class:`jax.sharding.Mesh` and XLA inserts the collectives (all-reduce /
all-gather / reduce-scatter / ppermute) over ICI within a slice and DCN across
slices.

Canonical mesh axes (any subset may be present, always in this order):

=======  =====================================================================
``dp``   pure data parallelism (params replicated, batch sharded)
``fsdp`` data parallelism with fully-sharded params (batch AND params sharded)
``tp``   tensor (a.k.a. model) parallelism — activations/weights sharded
``sp``   sequence/context parallelism — ring attention over this axis
``ep``   expert parallelism for MoE layers
``pp``   pipeline parallelism — GPipe stages (pipeline_parallel module)
=======  =====================================================================
"""

# Lazy re-exports (PEP 562): importing this package must not import jax —
# executor/driver processes stay jax-free so the platform (TPU vs CPU) is
# decided by the jax child process, not by whoever imported the package first.
_EXPORTS = {
    "AXIS_ORDER": "mesh",
    "build_hybrid_mesh": "mesh",
    "build_mesh": "mesh",
    "local_mesh": "mesh",
    "mesh_shape": "mesh",
    "shard_map": "collectives",
    "batch_sharding": "sharding",
    "batch_spec": "sharding",
    "data_axes": "sharding",
    "fsdp_param_specs": "sharding",
    "overlay_fsdp_specs": "sharding",
    "replicated": "sharding",
    "shard_batch": "sharding",
    "shard_params": "sharding",
    "collectives": None,
    "ring_attention": "ring_attention",
    "ring_attention_sharded": "ring_attention",
    "pipeline_apply": "pipeline_parallel",
    "stack_stage_params": "pipeline_parallel",
    "split_microbatches": "pipeline_parallel",
    "merge_microbatches": "pipeline_parallel",
}


def __getattr__(name):
    import importlib

    if name not in _EXPORTS:
        raise AttributeError(name)
    submodule = _EXPORTS[name] or name
    mod = importlib.import_module("tensorflowonspark_tpu.parallel." + submodule)
    return mod if _EXPORTS[name] is None else getattr(mod, name)


def __dir__():
    return sorted(_EXPORTS)
