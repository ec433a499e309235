"""ResNets — the performance workload (BASELINE.md north star).

Capability-parity with the reference's example models: ResNet-50 v1.5 for
ImageNet (/root/reference/examples/resnet/resnet_model.py — bottleneck blocks,
stride-2 in the 3x3, BN momentum 0.9 eps 1e-5) and ResNet-56 for CIFAR-10
(/root/reference/examples/resnet/resnet_cifar_model.py — 3 stages of 9 basic
blocks). TPU-first differences: bfloat16 compute (params float32) instead of
the reference's fp16+LossScaleOptimizer dance (resnet_imagenet_main.py:182-187
— bf16 needs no loss scaling), and BatchNorm statistics under pjit are global-
batch statistics (sync-BN for free, where the reference's
MultiWorkerMirroredStrategy used per-replica BN).
"""

import functools

import jax.numpy as jnp
import optax
from flax import linen as nn

from tensorflowonspark_tpu.models import register


def _norm_factory(bn_impl, train, dtype):
    """BatchNorm constructor for ``bn_impl``: ``"flax"`` = ``nn.BatchNorm``
    (global sync-BN under pjit), ``"pallas"`` = the fused-kernel
    :class:`~tensorflowonspark_tpu.ops.fused_bn.FusedBatchNorm` (per-shard
    stats — the BN-slice experiment, PERF.md Findings)."""
    if bn_impl == "pallas":
        import jax

        from tensorflowonspark_tpu.ops.fused_bn import FusedBatchNorm

        # same convention as the transformer's flash attention: interpret
        # (CPU emulation) everywhere but real TPU
        cls = functools.partial(
            FusedBatchNorm, interpret=jax.default_backend() != "tpu"
        )
    elif bn_impl == "flax":
        cls = nn.BatchNorm
    else:
        raise ValueError("bn_impl must be 'flax' or 'pallas', got {!r}".format(bn_impl))
    return functools.partial(
        cls, use_running_average=not train, momentum=0.9, epsilon=1e-5, dtype=dtype
    )


class BottleneckBlock(nn.Module):
    """ResNet v1.5 bottleneck: 1x1 → 3x3(stride) → 1x1, projection shortcut."""

    filters: int
    strides: int = 1
    dtype: jnp.dtype = jnp.float32
    bn_impl: str = "flax"

    @nn.compact
    def __call__(self, x, train=False):
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = _norm_factory(self.bn_impl, train, self.dtype)
        shortcut = x
        if x.shape[-1] != self.filters * 4 or self.strides != 1:
            shortcut = conv(self.filters * 4, (1, 1), strides=self.strides, name="proj")(x)
            shortcut = norm(name="proj_bn")(shortcut)
        y = nn.relu(norm(name="bn1")(conv(self.filters, (1, 1), name="conv1")(x)))
        y = nn.relu(
            norm(name="bn2")(conv(self.filters, (3, 3), strides=self.strides, name="conv2")(y))
        )
        y = norm(name="bn3", scale_init=nn.initializers.zeros)(
            conv(self.filters * 4, (1, 1), name="conv3")(y)
        )
        return nn.relu(y + shortcut)


class BasicBlock(nn.Module):
    """CIFAR ResNet basic block: 3x3 → 3x3."""

    filters: int
    strides: int = 1
    dtype: jnp.dtype = jnp.float32
    bn_impl: str = "flax"

    @nn.compact
    def __call__(self, x, train=False):
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = _norm_factory(self.bn_impl, train, self.dtype)
        shortcut = x
        if x.shape[-1] != self.filters or self.strides != 1:
            shortcut = conv(self.filters, (1, 1), strides=self.strides, name="proj")(x)
            shortcut = norm(name="proj_bn")(shortcut)
        y = nn.relu(norm(name="bn1")(conv(self.filters, (3, 3), strides=self.strides, name="conv1")(x)))
        y = norm(name="bn2", scale_init=nn.initializers.zeros)(
            conv(self.filters, (3, 3), name="conv2")(y)
        )
        return nn.relu(y + shortcut)


class ResNet(nn.Module):
    """Stage-configurable ResNet; ``bottleneck`` picks the block type."""

    stage_sizes: tuple
    filters: tuple
    num_classes: int = 1000
    bottleneck: bool = True
    stem: str = "imagenet"  # 7x7/2 + maxpool, "imagenet_s2d", or "cifar" 3x3
    dtype: jnp.dtype = jnp.float32
    bn_impl: str = "flax"

    @nn.compact
    def __call__(self, x, train=False):
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype)
        stem_bn = functools.partial(
            _norm_factory(self.bn_impl, train, self.dtype), name="stem_bn"
        )
        x = x.astype(self.dtype)
        if self.stem == "imagenet":
            x = conv(64, (7, 7), strides=2, padding=[(3, 3), (3, 3)], name="stem")(x)
            x = nn.relu(stem_bn()(x))
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        elif self.stem == "imagenet_s2d":
            # MXU-friendly stem (the MLPerf TPU ResNet space-to-depth trick):
            # a 7x7/2 conv on 3 input channels occupies 3 of the systolic
            # array's 128 input lanes; rearranging 2x2 pixel blocks into
            # channels ([B,H,W,3] -> [B,H/2,W/2,12]) turns it into a dense
            # stride-1 4x4 conv on 12 lanes — same downsampling, ~4x the MXU
            # occupancy, comparable receptive field (8 vs 7). Opt-in: the
            # stem weights are shaped differently from the reference's.
            b, h, w, c = x.shape
            if h % 2 or w % 2:
                raise ValueError(
                    "imagenet_s2d stem needs even spatial dims, got {}x{}".format(h, w)
                )
            x = x.reshape(b, h // 2, 2, w // 2, 2, c)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)
            x = conv(64, (4, 4), strides=1, padding="SAME", name="stem")(x)
            x = nn.relu(stem_bn()(x))
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        elif self.stem == "cifar":
            x = conv(self.filters[0], (3, 3), name="stem")(x)
            x = nn.relu(stem_bn()(x))
        else:
            raise ValueError(
                "unknown stem {!r}; expected 'imagenet', 'imagenet_s2d', or "
                "'cifar'".format(self.stem)
            )
        block_cls = BottleneckBlock if self.bottleneck else BasicBlock
        for stage, (n_blocks, filters) in enumerate(zip(self.stage_sizes, self.filters)):
            for i in range(n_blocks):
                strides = 2 if (i == 0 and stage > 0) else 1
                x = block_cls(
                    filters, strides=strides, dtype=self.dtype,
                    bn_impl=self.bn_impl,
                    name="stage{}_block{}".format(stage, i),
                )(x, train=train)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes, dtype=self.dtype, name="head")(x).astype(
            jnp.float32
        )


@register("resnet50")
def resnet50(num_classes=1000, dtype=jnp.float32, stem="imagenet", bn_impl="flax"):
    """ResNet-50 v1.5 (reference resnet_model.py layer spec [3,4,6,3]).
    ``stem="imagenet_s2d"`` opts into the space-to-depth stem (TPU MXU
    occupancy — see ResNet.__call__); ``bn_impl="pallas"`` into the fused
    BatchNorm kernels (per-shard stats — PERF.md Findings)."""
    return ResNet(
        stage_sizes=(3, 4, 6, 3), filters=(64, 128, 256, 512),
        num_classes=num_classes, bottleneck=True, stem=stem, dtype=dtype,
        bn_impl=bn_impl,
    )


@register("resnet56")
def resnet56(num_classes=10, dtype=jnp.float32):
    """ResNet-56 for CIFAR (reference resnet_cifar_model.py: 3 stages × 9
    basic blocks, filters 16/32/64)."""
    return ResNet(
        stage_sizes=(9, 9, 9), filters=(16, 32, 64),
        num_classes=num_classes, bottleneck=False, stem="cifar", dtype=dtype,
    )


@register("resnet18")
def resnet18(num_classes=1000, dtype=jnp.float32):
    return ResNet(
        stage_sizes=(2, 2, 2, 2), filters=(64, 128, 256, 512),
        num_classes=num_classes, bottleneck=False, stem="imagenet", dtype=dtype,
    )


def make_init_fn(model, image_size=224, channels=3):
    def init(rng):
        return model.init(rng, jnp.zeros((1, image_size, image_size, channels)), train=False)

    return init


def make_loss_fn(model, weight_decay=1e-4, label_smoothing=0.0, normalize=None):
    """Mutable loss for SyncDataParallel(compile_train_step(mutable=True)):
    threads batch_stats and applies the reference's L2 regularization
    (resnet_model.py applies wd to conv/dense kernels).

    ``normalize`` — optional device-side preprocess applied to
    ``batch["image"]`` before the model (e.g.
    :func:`tensorflowonspark_tpu.data.imagenet.device_normalize` when the
    feed ships raw uint8 pixels)."""
    import jax

    def loss_fn(params, model_state, batch):
        images = batch["image"] if normalize is None else normalize(batch["image"])
        logits, new_model_state = model.apply(
            {"params": params, **model_state}, images, train=True,
            mutable=["batch_stats"],
        )
        if label_smoothing > 0:
            num_classes = logits.shape[-1]
            onehot = jax.nn.one_hot(batch["label"], num_classes)
            onehot = onehot * (1 - label_smoothing) + label_smoothing / num_classes
            loss = optax.softmax_cross_entropy(logits, onehot).mean()
        else:
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["label"]
            ).mean()
        if weight_decay:
            l2 = sum(
                jnp.sum(jnp.square(p))
                for path, p in jax.tree_util.tree_flatten_with_path(params)[0]
                if path[-1].key == "kernel"
            )
            loss = loss + weight_decay * 0.5 * l2
        acc = jnp.mean(jnp.argmax(logits, -1) == batch["label"])
        return loss, (new_model_state, {"accuracy": acc})

    return loss_fn


def make_eval_fn(model, normalize=None):
    """``eval_fn(params, model_state, batch) -> (correct, count)`` for the
    reference's per-epoch top-1 eval (resnet_imagenet_main.py ran eval via
    model.evaluate; here it is a jitted metric over the eval input path)."""
    def eval_fn(params, model_state, batch):
        images = batch["image"] if normalize is None else normalize(batch["image"])
        logits = model.apply(
            {"params": params, **model_state}, images, train=False
        )
        correct = jnp.sum(jnp.argmax(logits, -1) == batch["label"])
        return correct, batch["label"].shape[0]

    return eval_fn


def make_predict_fn(model, normalize=None):
    def predict_fn(params, model_state, batch):
        images = batch["image"] if normalize is None else normalize(batch["image"])
        logits = model.apply(
            {"params": params, **model_state}, images, train=False
        )
        return jnp.argmax(logits, -1)

    return predict_fn
