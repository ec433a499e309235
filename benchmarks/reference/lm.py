"""Plain float32 reference for the ``lm`` family: the dense decoder LM's
forward pass, loss, gradients and AdamW step in straightforward
``jax.numpy`` — no kernels, no flax, nothing imported from the program.

The block follows ``configs/lm1024.json``: token embedding, then per layer
RMSNorm (eps 1e-6) -> q/k/v projections without bias -> rotary positions
(half split, base 10000, positions restart in every packed segment) ->
causal attention fenced to the segment -> output projection, residual;
RMSNorm -> tanh-GELU MLP, residual; final RMSNorm and an untied head. The
loss is the mean next-token cross-entropy over targets whose source and
target share a real (non-zero) segment.

``init_params`` is the benchmark's seeded weights: the ``lm`` family hands
the same function to the program, so both sides start from identical
float32 values without either taking anything the other made.

``quant="fp8"`` is the control: every matrix product takes its two operands
rounded to float8 (e4m3, one scale per tensor, straight-through gradient) —
the precision step below the configuration's bfloat16.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import sketch
from benchmarks.reference.control import fake_quant

_EPS = 1e-6


def leaf_shapes(cfg):
    """``{path: (shape, init std)}`` in the parameter tree's own order."""
    d, h, ff, v = cfg["d_model"], cfg["n_heads"], cfg["d_ff"], cfg["vocab_size"]
    hd = d // h
    shapes = {("embed", "embedding"): ((v, d), 1.0)}
    for i in range(cfg["n_layers"]):
        layer = "layer_{}".format(i)
        shapes[(layer, "ln1", "scale")] = ((d,), None)
        for name in ("q", "k", "v"):
            shapes[(layer, "attn", name, "kernel")] = ((d, h, hd), d ** -0.5)
        shapes[(layer, "attn", "o", "kernel")] = ((h, hd, d), d ** -0.5)
        shapes[(layer, "ln2", "scale")] = ((d,), None)
        shapes[(layer, "mlp", "wi", "kernel")] = ((d, ff), d ** -0.5)
        shapes[(layer, "mlp", "wo", "kernel")] = ((ff, d), ff ** -0.5)
    shapes[("ln_f", "scale")] = ((d,), None)
    shapes[("lm_head", "kernel")] = ((d, v), d ** -0.5)
    return shapes


def init_params(key, cfg):
    """Seeded float32 weights as a nested dict (the flax naming the program's
    model uses, so the same tree serves both sides)."""
    tree = {}
    for index, (path, (shape, std)) in enumerate(leaf_shapes(cfg).items()):
        if std is None:
            leaf = jnp.ones(shape, jnp.float32)
        else:
            leaf = std * jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaf
    return tree


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, fake_quant(a, quant), fake_quant(b, quant))


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + _EPS) * scale


def _rope(x, positions):
    half = x.shape[-1] // 2
    freqs = 10000.0 ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[:, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _block(x, layer, positions, seg, quant):
    h = _rms(x, layer["ln1"]["scale"])
    q = _rope(_mm("bld,dhk->blhk", h, layer["attn"]["q"]["kernel"], quant), positions)
    k = _rope(_mm("bld,dhk->blhk", h, layer["attn"]["k"]["kernel"], quant), positions)
    v = _mm("bld,dhk->blhk", h, layer["attn"]["v"]["kernel"], quant)
    scores = _mm("bqhk,bshk->bhqs", q, k, quant) / math.sqrt(q.shape[-1])
    length = x.shape[1]
    mask = (jnp.arange(length)[:, None] >= jnp.arange(length)[None, :])[None, None]
    mask = mask & (seg[:, None, :, None] == seg[:, None, None, :])
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    out = _mm("bhqs,bshk->bqhk", probs, v, quant)
    x = x + _mm("blhk,hkd->bld", out, layer["attn"]["o"]["kernel"], quant)
    h = _rms(x, layer["ln2"]["scale"])
    h = jax.nn.gelu(_mm("bld,df->blf", h, layer["mlp"]["wi"]["kernel"], quant), approximate=True)
    return x + _mm("blf,fd->bld", h, layer["mlp"]["wo"]["kernel"], quant)


def stack_layers(params, cfg):
    """The flax-shaped tree with its ``layer_<i>`` subtrees stacked along a
    new leading axis, so the layers run as one ``lax.scan``."""
    layers = [params["layer_{}".format(i)] for i in range(cfg["n_layers"])]
    rest = {k: v for k, v in params.items() if not k.startswith("layer_")}
    rest["layers"] = jax.tree.map(lambda *leaves: jnp.stack(leaves), *layers)
    return rest


def leaf_norms(stacked, other=None):
    """Norm of every leaf (of ``stacked - other`` where given), keyed by the
    flax path: a stacked leaf gives one norm per layer."""
    if other is not None:
        stacked = jax.tree.map(jnp.subtract, stacked, other)
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(stacked)[0]:
        names = [p.key for p in path]
        if names[0] == "layers":
            per_layer = jnp.sqrt(jnp.sum(jnp.square(leaf), axis=tuple(range(1, leaf.ndim))))
            for i in range(leaf.shape[0]):
                out["/".join(["layer_{}".format(i)] + names[1:])] = per_layer[i]
        else:
            out["/".join(names)] = jnp.sqrt(jnp.sum(jnp.square(leaf)))
    return out


def leaf_sketches(stacked, key):
    """``sketch.leaf_sketch`` of every leaf, keyed as ``leaf_norms`` keys them."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(stacked)[0]:
        names = [p.key for p in path]
        if names[0] == "layers":
            for i in range(leaf.shape[0]):
                name = "/".join(["layer_{}".format(i)] + names[1:])
                out[name] = sketch.leaf_sketch(leaf[i], key, name)
        else:
            out["/".join(names)] = sketch.leaf_sketch(leaf, key, "/".join(names))
    return out


def loss_sum(params, batch, cfg, quant=None):
    """Sum (not mean) of the valid targets' cross-entropy over the rows of
    ``batch``, on the stacked tree: rows can then be processed a few at a
    time and added."""
    tokens, seg, pos = batch["tokens"], batch["segment_ids"], batch["positions"]
    inputs, seg_in, pos_in = tokens[:, :-1], seg[:, :-1], pos[:, :-1]
    x = params["embed"]["embedding"][inputs]

    @jax.checkpoint
    def body(x, layer):
        return _block(x, layer, pos_in, seg_in, quant), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _rms(x, params["ln_f"]["scale"])
    logits = _mm("bld,dv->blv", x, params["lm_head"]["kernel"], quant)
    targets = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    valid = ((seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] > 0)).astype(jnp.float32)
    return jnp.sum((logz - picked) * valid)


def valid_targets(batch):
    seg = np.asarray(batch["segment_ids"])
    return float(((seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] > 0)).sum())


def make_grad_fn(cfg, quant=None):
    """``grad_fn(params, rows, scale) -> (loss, grads)`` for a block of rows:
    ``scale`` is one over the whole batch's valid-target count, so blocks add
    up to the batch's mean loss and its gradient."""

    def block_loss(params, rows, scale):
        with jax.default_matmul_precision("highest"):
            return loss_sum(params, rows, cfg, quant) * scale

    return jax.value_and_grad(block_loss)


def adamw_init(params):
    return {
        "mu": jax.tree.map(jnp.zeros_like, params),
        "nu": jax.tree.map(jnp.zeros_like, params),
        "count": jnp.zeros((), jnp.float32),
    }


def adamw_update(params, grads, state, opt):
    """One AdamW step as optax.adamw composes it: bias-corrected moments,
    decoupled weight decay added to the update, then the learning rate."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    count = state["count"] + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, state["nu"], grads)

    def step(p, m, n):
        update = (m / (1 - b1 ** count)) / (jnp.sqrt(n / (1 - b2 ** count)) + eps)
        return p - opt["learning_rate"] * (update + opt["weight_decay"] * p)

    return jax.tree.map(step, params, mu, nu), {"mu": mu, "nu": nu, "count": count}


def follow(cfg, key, batches, devices, quant=None):
    """Train from the seeded weights over ``batches`` (host dicts of int32
    ``[rows, seq+1]`` arrays). Returns each step's loss, the first step's
    gradient norm and sketch per leaf and the norm per leaf of the parameters'
    change over all the steps. Rows go through the gradient function one per device
    at a time, so a packed row's 4096 x 4096 float32 scores fit."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("rows",))
    replicated, by_row = NamedSharding(mesh, P()), NamedSharding(mesh, P("rows"))
    init = jax.jit(lambda k: stack_layers(init_params(k, cfg), cfg), out_shardings=replicated)
    grad_fn = jax.jit(make_grad_fn(cfg, quant), out_shardings=replicated)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
    update = jax.jit(functools.partial(adamw_update, opt=cfg["optimizer"]), donate_argnums=(0, 2))
    norms = jax.jit(leaf_norms)
    sketches = jax.jit(leaf_sketches)

    params = init(key)
    state = jax.jit(adamw_init, out_shardings=replicated)(params)
    losses, first_grad = [], None
    for batch in batches:
        scale = np.float32(1.0 / max(valid_targets(batch), 1.0))
        rows = batch["tokens"].shape[0]
        loss, grads = 0.0, None
        for lo in range(0, rows, len(devices)):
            block = {k: jax.device_put(np.asarray(v[lo:lo + len(devices)]), by_row)
                     for k, v in batch.items()}
            part, g = grad_fn(params, block, scale)
            loss += float(part)
            grads = g if grads is None else add(grads, g)
        losses.append(loss)
        if first_grad is None:
            first_grad = {k: float(v) for k, v in jax.device_get(norms(grads)).items()}
            first_sketch = {k: v.tolist() for k, v in jax.device_get(sketches(grads, key)).items()}
        params, state = update(params, grads, state)
        del grads
    change = {k: float(v) for k, v in jax.device_get(norms(params, init(key))).items()}
    return {"losses": losses, "first_grad": first_grad, "first_grad_sketch": first_sketch,
            "param_change": change}
