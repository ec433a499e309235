"""TPU pallas kernels for the hot ops.

The reference had no kernels of its own — its hot loops were TensorFlow's
CUDA/NCCL internals (SURVEY.md §2.6). Here the compute path is XLA, and pallas
covers the places XLA needs help; kernels ship with an ``interpret`` mode so
numerics are testable on CPU.
"""

_EXPORTS = {
    "flash_attention": "flash_attention",
    "fused_batch_norm": "fused_bn",
    "FusedBatchNorm": "fused_bn",
}


def __getattr__(name):
    import importlib

    if name not in _EXPORTS:
        raise AttributeError(name)
    mod = importlib.import_module("tensorflowonspark_tpu.ops." + _EXPORTS[name])
    return getattr(mod, name) if name != _EXPORTS[name] else mod


def __dir__():
    return sorted(_EXPORTS)
