"""Training strategies, step builders and checkpointing for the TPU runtime.

This package is the replacement for the reference's reliance on
``tf.distribute.*Strategy`` + TF checkpointing (SURVEY.md §2.6/§5): sync data
parallelism is a pjit program over a ``jax.sharding.Mesh`` with XLA collectives
over ICI, and checkpoint/resume is orbax.
"""

# Lazy re-exports (PEP 562): keep `import tensorflowonspark_tpu.train` (and
# `from ... import checkpoint`) jax-free; jax loads only when a strategy or
# checkpoint function is actually touched.
_EXPORTS = {
    "SyncDataParallel": "strategy",
    "TrainState": "strategy",
    "steps_per_worker": "strategy",
    "run_steps": "strategy",
    "checkpoint": None,
    "strategy": None,
    "export": None,
    "metrics": None,
    "export_model": "export",
    "load_model": "export",
    "TimeHistory": "metrics",
    "build_stats": "metrics",
}


def __getattr__(name):
    import importlib

    if name not in _EXPORTS:
        raise AttributeError(name)
    submodule = _EXPORTS[name] or name
    mod = importlib.import_module("tensorflowonspark_tpu.train." + submodule)
    return mod if _EXPORTS[name] is None else getattr(mod, name)


def __dir__():
    return sorted(_EXPORTS)
