"""Plain float32 reference for the ``image`` family: ResNet-50 v1.5's
training forward pass, loss, gradients and SGD-with-momentum step in
``jax.numpy`` / ``lax`` primitives — no flax, no kernels, nothing imported
from the program.

Follows ``configs/resnet50.json`` and He et al. 2015 with the v1.5 stride
placement: 7x7/2 stem, BatchNorm, ReLU, 3x3/2 max pool; four stages of
bottleneck blocks (1x1 -> 3x3 carrying the stride -> 1x1 x4, projection
shortcut where the shape changes), every convolution without bias and
padded as XLA's SAME (which a stride-2 3x3 pads on the high side only,
where torchvision pads both; the program does the same) and
followed by BatchNorm over the whole batch (float32 statistics, biased
variance, eps 1e-5, running averages at momentum 0.9); global mean pool and a
dense head with bias. The feed is uint8; the channel means are subtracted
here as on the device. Loss: mean cross-entropy plus ``weight_decay / 2``
times the squared norm of every convolution and head kernel.

``init_params`` is the benchmark's seeded weights, handed to the program by
the ``image`` family, so both sides start from identical float32 values.
``quant="fp8"`` is the control: every convolution and the head take both
operands rounded to float8 (e4m3, one scale per tensor, straight-through).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import sketch
from benchmarks.reference.control import fake_quant

CHANNEL_MEANS = (123.68, 116.78, 103.94)


def _blocks(cfg):
    """(name, filters, stride, has_projection) for every bottleneck block."""
    out, channels = [], 64
    for stage, (n, filters) in enumerate(zip(cfg["stage_sizes"], cfg["filters"])):
        for i in range(n):
            stride = 2 if (i == 0 and stage > 0) else 1
            out.append(("stage{}_block{}".format(stage, i), filters, stride,
                        channels != filters * 4 or stride != 1, channels))
            channels = filters * 4
    return out


def init_variables(key, cfg):
    """``{"params": ..., "batch_stats": ...}`` in the flax naming of the
    program's ResNet, float32, from the seed."""
    params, stats, counter = {}, {}, [0]

    def conv(shape):
        fan_in = shape[0] * shape[1] * shape[2]
        counter[0] += 1
        return (2.0 / fan_in) ** 0.5 * jax.random.normal(
            jax.random.fold_in(key, counter[0]), shape, jnp.float32)

    def bn(channels):
        return ({"scale": jnp.ones((channels,), jnp.float32), "bias": jnp.zeros((channels,), jnp.float32)},
                {"mean": jnp.zeros((channels,), jnp.float32), "var": jnp.ones((channels,), jnp.float32)})

    def last_bn(channels):
        # the last BatchNorm of a block starts at scale 0 (Goyal et al. 2017,
        # MLPerf's and the program's own init): the block starts as its shortcut
        p, s = bn(channels)
        return dict(p, scale=jnp.zeros((channels,), jnp.float32)), s

    params["stem"] = {"kernel": conv((7, 7, 3, 64))}
    params["stem_bn"], stats["stem_bn"] = bn(64)
    for name, filters, _stride, projects, channels in _blocks(cfg):
        p, s = {}, {}
        p["conv1"] = {"kernel": conv((1, 1, channels, filters))}
        p["conv2"] = {"kernel": conv((3, 3, filters, filters))}
        p["conv3"] = {"kernel": conv((1, 1, filters, filters * 4))}
        p["bn1"], s["bn1"] = bn(filters)
        p["bn2"], s["bn2"] = bn(filters)
        p["bn3"], s["bn3"] = last_bn(filters * 4)
        if projects:
            p["proj"] = {"kernel": conv((1, 1, channels, filters * 4))}
            p["proj_bn"], s["proj_bn"] = bn(filters * 4)
        params[name], stats[name] = p, s
    features = cfg["filters"][-1] * 4
    counter[0] += 1
    params["head"] = {
        "kernel": features ** -0.5 * jax.random.normal(
            jax.random.fold_in(key, counter[0]), (features, cfg["num_classes"]), jnp.float32),
        "bias": jnp.zeros((cfg["num_classes"],), jnp.float32),
    }
    return {"params": params, "batch_stats": stats}


def _conv(x, kernel, stride, padding, quant):
    return jax.lax.conv_general_dilated(
        fake_quant(x, quant), fake_quant(kernel, quant), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p, cfg):
    """Training-mode BatchNorm; returns the output and the batch's mean and
    biased variance (for the running averages)."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    y = (x - mean) * jax.lax.rsqrt(var + cfg["bn_epsilon"]) * p["scale"] + p["bias"]
    return y, {"mean": mean, "var": var}


def _bottleneck(x, p, stride, cfg, quant):
    batch_stats = {}

    def conv_bn(x, conv, bn, stride):
        kernel = p[conv]["kernel"]
        y, batch_stats[bn] = _bn(_conv(x, kernel, stride, "SAME", quant), p[bn], cfg)
        return y

    shortcut = conv_bn(x, "proj", "proj_bn", stride) if "proj" in p else x
    y = jax.nn.relu(conv_bn(x, "conv1", "bn1", 1))
    y = jax.nn.relu(conv_bn(y, "conv2", "bn2", stride))
    y = conv_bn(y, "conv3", "bn3", 1)
    return jax.nn.relu(y + shortcut), batch_stats


def loss_fn(params, batch, cfg, quant=None):
    """Mean loss of the batch and the batch statistics of every BatchNorm."""
    x = batch["image"].astype(jnp.float32) - jnp.asarray(CHANNEL_MEANS, jnp.float32)
    stats = {}
    x, stats["stem_bn"] = _bn(
        _conv(x, params["stem"]["kernel"], 2, [(3, 3), (3, 3)], quant), params["stem_bn"], cfg)
    x = jax.nn.relu(x)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                              ((0, 0), (1, 1), (1, 1), (0, 0)))
    for name, _filters, stride, _projects, _channels in _blocks(cfg):
        block = jax.checkpoint(functools.partial(_bottleneck, stride=stride, cfg=cfg, quant=quant))
        x, stats[name] = block(x, params[name])
    x = jnp.mean(x, axis=(1, 2))
    logits = jnp.dot(fake_quant(x, quant), fake_quant(params["head"]["kernel"], quant))
    logits = logits + params["head"]["bias"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, batch["label"][:, None], axis=-1)[:, 0]
    loss = jnp.mean(logz - picked)
    l2 = sum(
        jnp.sum(jnp.square(leaf))
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
        if path[-1].key == "kernel"
    )
    return loss + cfg["weight_decay"] * 0.5 * l2, stats


def leaf_norms(tree, other=None):
    if other is not None:
        tree = jax.tree.map(jnp.subtract, tree, other)
    return {
        "/".join(p.key for p in path): jnp.sqrt(jnp.sum(jnp.square(leaf)))
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def leaf_sketches(tree, key):
    named = {"/".join(p.key for p in path): leaf for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
    return {name: sketch.leaf_sketch(leaf, key, name) for name, leaf in named.items()}


def follow(cfg, key, batches, devices, quant=None):
    """Train from the seeded weights over ``batches`` (host dicts: uint8
    ``image [B, H, W, 3]``, int ``label [B]``). The whole batch goes through
    at once — BatchNorm's statistics are the batch's — with every block
    recomputed in the backward pass so float32 activations fit; on several
    devices the batch is sharded over them and the statistics stay global.
    Returns each step's loss, the first step's gradient norm and sketch per
    leaf and the norm per leaf of the parameters' change over all the steps."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("rows",))
    replicated, by_row = NamedSharding(mesh, P()), NamedSharding(mesh, P("rows"))
    opt = cfg["optimizer"]
    init = jax.jit(lambda k: init_variables(k, cfg)["params"], out_shardings=replicated)

    def step(params, trace, batch, key):
        # the key is an argument: closed over, it would be a constant of the
        # program and every seed would compile the step again
        with jax.default_matmul_precision("highest"):
            (loss, _stats), grads = jax.value_and_grad(
                functools.partial(loss_fn, cfg=cfg, quant=quant), has_aux=True)(params, batch)
        trace = jax.tree.map(lambda t, g: g + opt["momentum"] * t, trace, grads)
        params = jax.tree.map(lambda p, t: p - opt["learning_rate"] * t, params, trace)
        return params, trace, loss, (leaf_norms(grads), leaf_sketches(grads, key))

    step = jax.jit(step, donate_argnums=(0, 1), out_shardings=replicated)
    params = init(key)
    trace = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p), out_shardings=replicated)(params)
    losses, first_grad = [], None
    for batch in batches:
        placed = {k: jax.device_put(np.asarray(v), by_row) for k, v in batch.items()}
        params, trace, loss, grad_readings = step(params, trace, placed, key)
        losses.append(float(loss))
        if first_grad is None:
            norms, sketches = jax.device_get(grad_readings)
            first_grad = {k: float(v) for k, v in norms.items()}
            first_sketch = {k: v.tolist() for k, v in sketches.items()}
    change = jax.jit(leaf_norms)(params, init(key))
    return {"losses": losses, "first_grad": first_grad, "first_grad_sketch": first_sketch,
            "param_change": {k: float(v) for k, v in jax.device_get(change).items()}}
