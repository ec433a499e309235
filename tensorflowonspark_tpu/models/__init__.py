"""flax model zoo covering the reference's example workloads (SURVEY.md §2.3)
plus the TPU-era flagship transformer:

* :mod:`~tensorflowonspark_tpu.models.mnist` — MLP/CNN MNIST classifiers
  (reference examples/mnist/keras/mnist_spark.py model).
* :mod:`~tensorflowonspark_tpu.models.resnet` — ResNet-50 v1.5 (ImageNet) and
  ResNet-56 (CIFAR) (reference examples/resnet/resnet_model.py,
  resnet_cifar_model.py).
* :mod:`~tensorflowonspark_tpu.models.segmentation` — U-Net image segmentation
  (reference examples/segmentation/segmentation_spark.py).
* :mod:`~tensorflowonspark_tpu.models.transformer` — decoder-only LM with
  ring-attention sequence parallelism; the long-context flagship.
* :mod:`~tensorflowonspark_tpu.models.decoder` — decoder LMs assembled from a
  per-layer plan: latent attention, routed + shared experts (a chip's share
  of them), hyper-connected residual streams.

Every module exposes ``create_model(**cfg)`` plus ``make_*_fn`` builders that
plug into :class:`tensorflowonspark_tpu.train.SyncDataParallel`.
"""

_REGISTRY = {}


def register(name):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn

    return deco


def get_model(name, **cfg):
    """Construct a registered model by name (e.g. 'mnist_cnn', 'resnet50',
    'resnet56', 'unet', 'transformer', 'decoder')."""
    if name not in _REGISTRY:
        # import lazily so get_model('resnet50') works without the caller
        # importing the module first
        from tensorflowonspark_tpu.models import decoder, mnist, resnet, segmentation, transformer  # noqa: F401
    if name not in _REGISTRY:
        raise KeyError("unknown model {!r}; known: {}".format(name, sorted(_REGISTRY)))
    return _REGISTRY[name](**cfg)
