"""Plain float32 reference for the ``ssm_lm`` family: the decoder-hybrid-decoder
of ``configs/phi-4-mini-flash.json`` (``model_type`` ``phi4flash``; SambaY,
arXiv:2507.06607) — Mamba layers and windowed differential attention into one
full layer, then gated memory units and cross attention reading what the
junction kept — forward pass, next-token loss, gradients and AdamW step in
straightforward ``jax.numpy``: the recurrence a ``lax.scan`` over positions,
masks written out, no kernels, nothing imported from the program.

``d`` hidden, ``u = LayerNorm(x)`` (weight and bias, eps ``layer_norm_eps``);
every layer ``x <- x + mixer(LayerNorm_1(x))``, ``x <- x + MLP(LayerNorm_2(x))``;
no positional encoding anywhere. ``l = first_layer + i`` is the layer's place in
the published model of ``N = model_layers`` layers and decides its kind
(:func:`layer_kind`) and its ``lambda_init``:

* **MLP** (all layers): ``(SiLU(u W_gate) * (u W_up)) W_down`` (the published
  ``[g, y] = u W_1`` in two halves), no bias.
* **Mamba** (``l`` even, ``l <= N/2``). ``D = 2 d`` channels, ``S`` states,
  ``R = ceil(d / 16)``: ``[xs, z] = u W_in``; ``c_t = SiLU(b + sum_{j<4} w_j *
  xs_{t-j})`` per channel, a term of another document or from before the row's
  start zero; ``[r, B, C] = c W_x``; ``Delta = softplus(r W_dt + b_dt)``; ``A =
  -exp(A_log)``; ``h_t = exp(Delta_t (x) A) * h_{t-1} + (Delta_t * c_t) (x)
  B_t``, ``h = 0`` before each document's first position; ``y_t = h_t C_t +
  skip * c_t``; result ``(y * SiLU(z)) W_out``. Layer ``N/2`` hands ``y`` on:
  the memory ``m``.
* **Differential attention** (``l`` odd, ``l <= N/2 + 1``; within
  ``sliding_window`` where ``l < N/2``, the whole document at ``N/2 + 1``):
  ``q, k, v = u W + b`` (H, K, K heads of ``w``). Query heads ``2i, 2i + 1`` are
  pair ``i``; key heads ``2j, 2j + 1`` and ``V_j = [v_2j, v_2j+1]`` (``2 w``
  wide) key/value pair ``j``; pair ``i`` reads ``j = i // (H / K)``. ``A1 =
  softmax(q_2i k_2j^T / sqrt(w))``, ``A2 = softmax(q_2i+1 k_2j+1^T / sqrt(w))``
  over the keys the rule shows (same non-zero document, not after the query,
  and on a windowed layer less than ``sliding_window`` positions before it, the
  query counted); ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)``,
  ``lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)``; ``o_i = RMSNorm((A1 - lambda A2)
  V_j) * (1 - lambda_init(l))`` (learned weight over ``2 w``, eps 1e-5); result
  ``concat_i(o_i) W_o + b_o``. Layer ``N/2 + 1`` hands its ``k`` and ``v`` on.
* **Gated memory unit** (``l`` even, ``l > N/2``): ``(SiLU(u W_1) * m) W_2``.
* **Cross attention** (``l`` odd, ``l > N/2 + 1``): ``q = u W_q + b``; keys and
  values are layer ``N/2 + 1``'s as it computed them; the differential form
  with its own lambdas and sub-norm, the whole-document rule.
* Final LayerNorm; logits ``LayerNorm(x) E^T``, ``E`` the embedding (tied);
  next-token cross-entropy over the valid targets (a target is valid when it
  and the position it is predicted from share a real document).

Departures from the published description (each under ``assumed`` in the
configuration): the layers held are 14-19 of 32 and the vocabulary a slice;
Mamba's sizes and initial values, the split at N/2, the differential form and
where the biases are follow the family's code, not the config; ``m`` includes
the skip; the window counts the query; the MLP's first matrix is two
parameters; the convolution's row ``j`` is the tap ``j`` positions back.

``quant="fp8"`` is the control: every matrix product takes its operands
rounded to float8 (the recurrence and the convolution have no matrix product
and stay float32). Memory: rows one at a time, queries a block at a time, the
scan in channel blocks, every layer recomputed in the backward pass; AdamW's
moments on the host, the update leaf by leaf.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.control import fake_quant
from benchmarks.reference.moe_lm import _names, adamw_leaf, leaf_norms, leaf_sketches, valid_targets

QUERY_BLOCK = 512
CHANNEL_BLOCK = 1280
SUBNORM_EPS = 1e-5


def sizes(cfg):
    """``(D, S, R, taps)``: channels, states, the step's rank and the
    convolution's taps, by the family's rules (the configuration names none):
    expand 2, 16 states, ``ceil(d / 16)``, 4 taps."""
    d = cfg["hidden_size"]
    return 2 * d, 16, -(-d // 16), 4


def layer_kind(cfg, index):
    """``(kind, l)`` of the ``index``-th layer held: ``mamba``, ``window``,
    ``full``, ``gmu`` or ``cross``, and its place in the published model."""
    at = cfg.get("first_layer", 0) + index
    half = (cfg.get("model_layers") or cfg["num_hidden_layers"]) // 2
    if at % cfg["mb_per_layer"] == 0:
        return ("mamba" if at <= half else "gmu"), at
    if at > half + 1:
        return "cross", at
    return ("window" if at < half and cfg.get("sliding_window") else "full"), at


def hands_on(cfg, at):
    half = (cfg.get("model_layers") or cfg["num_hidden_layers"]) // 2
    return at in (half, half + 1)


def lambda_init(at):
    return 0.8 - 0.6 * math.exp(-0.3 * at)


def leaf_shapes(cfg):
    """``{path: (shape, init)}``: ``init`` a normal's std, ``("const",
    value)``, ``"a_log"`` (``log(1..S)`` a channel) or ``"dt_bias"`` (the
    inverse softplus of a step log-uniform in [1e-3, 0.1])."""
    d, v, heads, kv = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    width, wide = d // heads, cfg["intermediate_size"]
    inner, states, rank, taps = sizes(cfg)
    # the head's matrix too (tied): fan-in d; at d 2560 the family's initializer_range of 0.02
    shapes = {("embed", "embedding"): ((v, d), d ** -0.5)}
    for i in range(cfg["num_hidden_layers"]):
        layer, (kind, _) = "layer_{}".format(i), layer_kind(cfg, i)
        for norm in ("ln1", "ln2"):
            shapes[(layer, norm, "scale")] = ((d,), ("const", 1.0))
            shapes[(layer, norm, "bias")] = ((d,), ("const", 0.0))
        if kind == "mamba":
            shapes[(layer, "mamba", "in_proj", "kernel")] = ((d, 2 * inner), d ** -0.5)
            shapes[(layer, "mamba", "conv_kernel")] = ((taps, inner), taps ** -0.5)
            shapes[(layer, "mamba", "conv_bias")] = ((inner,), ("const", 0.0))
            shapes[(layer, "mamba", "x_proj", "kernel")] = ((inner, rank + 2 * states), inner ** -0.5)
            shapes[(layer, "mamba", "dt_proj", "kernel")] = ((rank, inner), rank ** -0.5)
            shapes[(layer, "mamba", "dt_proj", "bias")] = ((inner,), "dt_bias")
            shapes[(layer, "mamba", "a_log")] = ((inner, states), "a_log")
            shapes[(layer, "mamba", "skip")] = ((inner,), ("const", 1.0))
            shapes[(layer, "mamba", "out_proj", "kernel")] = ((inner, d), inner ** -0.5)
        elif kind == "gmu":
            shapes[(layer, "gmu", "in_proj", "kernel")] = ((d, inner), d ** -0.5)
            shapes[(layer, "gmu", "out_proj", "kernel")] = ((inner, d), inner ** -0.5)
        else:
            for name, n in (("q", heads),) + ((("k", kv), ("v", kv)) if kind != "cross" else ()):
                shapes[(layer, "attn", name, "kernel")] = ((d, n, width), d ** -0.5)
                shapes[(layer, "attn", name, "bias")] = ((n, width), ("const", 0.0))
            for name in ("q1", "k1", "q2", "k2"):
                shapes[(layer, "attn", "lambda_" + name)] = ((width,), 0.1)
            shapes[(layer, "attn", "subln", "scale")] = ((2 * width,), ("const", 1.0))
            shapes[(layer, "attn", "o", "kernel")] = ((heads // 2, 2 * width, d), (heads * width) ** -0.5)
            shapes[(layer, "attn", "o", "bias")] = ((d,), ("const", 0.0))
        shapes[(layer, "mlp", "gate", "kernel")] = ((d, wide), d ** -0.5)
        shapes[(layer, "mlp", "up", "kernel")] = ((d, wide), d ** -0.5)
        shapes[(layer, "mlp", "down", "kernel")] = ((wide, d), wide ** -0.5)
    shapes[("ln_f", "scale")] = ((d,), ("const", 1.0))
    shapes[("ln_f", "bias")] = ((d,), ("const", 0.0))
    return shapes


def init_params(key, cfg):
    """Seeded float32 weights as a nested dict, named as the program's model
    names its parameters."""
    tree = {}
    for index, (path, (shape, init)) in enumerate(leaf_shapes(cfg).items()):
        draw = jax.random.fold_in(key, index)
        if isinstance(init, tuple):
            leaf = init[1] * jnp.ones(shape, jnp.float32)
        elif init == "a_log":
            leaf = jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
        elif init == "dt_bias":
            step = jnp.exp(jax.random.uniform(draw, shape, jnp.float32) * (math.log(0.1) - math.log(1e-3))
                           + math.log(1e-3))
            leaf = step + jnp.log(-jnp.expm1(-step))
        else:
            leaf = init * jax.random.normal(draw, shape, jnp.float32)
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaf
    return tree


def parameter_count(cfg):
    return sum(int(np.prod(shape)) for shape, _ in leaf_shapes(cfg).values())


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, fake_quant(a, quant), fake_quant(b, quant))


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def conv(xs, kernel, bias, ids):
    """``SiLU(b + sum_j w_j xs_{t-j})``, a term of another document (or
    before the row) zero. ``xs`` ``[B, L, D]``, ``ids`` ``[B, L]``."""
    length = xs.shape[1]
    at = jnp.arange(length)
    total = bias + kernel[0] * xs
    for back in range(1, kernel.shape[0]):
        source = jnp.maximum(at - back, 0)
        seen = (at - back >= 0)[None, :] & (ids[:, source] == ids)
        total = total + kernel[back] * jnp.where(seen[..., None], xs[:, source], 0.0)
    return jax.nn.silu(total)


def scan(delta, c, b_t, c_t, a, skip, ids):
    """The recurrence over the positions of every row, a plain ``lax.scan``;
    channels a block at a time. ``delta``, ``c`` ``[B, L, D]``, ``b_t``,
    ``c_t`` ``[B, L, S]``, ``a`` ``[D, S]``: ``y`` ``[B, L, D]``."""
    length, inner = delta.shape[1], delta.shape[2]
    starts = jnp.concatenate([jnp.ones((ids.shape[0], 1), bool), ids[:, 1:] != ids[:, :-1]], axis=1)
    size = math.gcd(inner, CHANNEL_BLOCK)

    def row(delta, c, b_t, c_t, starts, a):
        def step(h, now):
            d, x, bb, cc, first = now
            h = jnp.where(first, 0.0, jnp.exp(d[:, None] * a) * h) + (d * x)[:, None] * bb[None, :]
            return h, jnp.sum(h * cc[None, :], axis=-1)

        return jax.lax.scan(step, jnp.zeros(a.shape, jnp.float32), (delta, c, b_t, c_t, starts))[1]

    @jax.checkpoint
    def block(first):
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, first, size, axis=-1)  # noqa: E731
        a_block = jax.lax.dynamic_slice_in_dim(a, first, size, axis=0)
        return jax.vmap(row, in_axes=(0, 0, 0, 0, 0, None))(cut(delta), cut(c), b_t, c_t, starts, a_block)

    y = jax.lax.map(block, jnp.arange(0, inner, size))  # [blocks, B, L, size]
    y = jnp.moveaxis(y, 0, 2).reshape(delta.shape[0], length, inner)
    return y + skip * c


def mamba(u, p, ids, cfg, quant=None):
    """``(result, y)``: ``y`` the scan's output before the gate (the memory)."""
    inner, states, rank, _ = sizes(cfg)
    xz = _mm("bld,de->ble", u, p["in_proj"]["kernel"], quant)
    xs, z = xz[..., :inner], xz[..., inner:]
    c = conv(xs, p["conv_kernel"], p["conv_bias"], ids)
    low = _mm("ble,er->blr", c, p["x_proj"]["kernel"], quant)
    delta = jax.nn.softplus(_mm("blr,re->ble", low[..., :rank], p["dt_proj"]["kernel"], quant) + p["dt_proj"]["bias"])
    y = scan(delta, c, low[..., rank:rank + states], low[..., rank + states:], -jnp.exp(p["a_log"]), p["skip"], ids)
    return _mm("ble,ed->bld", y * jax.nn.silu(z), p["out_proj"]["kernel"], quant), y


def visible(q_ids, q_at, k_ids, k_at, window):
    """The mask written out, ``bool [B, queries, keys]``."""
    behind = q_at[:, :, None] - k_at[:, None, :]
    seen = (q_ids[:, :, None] == k_ids[:, None, :]) & (q_ids[:, :, None] > 0) & (behind >= 0)
    return seen if window is None else seen & (behind < window)


def attention(u, p, ids, cfg, kind, at, shared=None, quant=None):
    """``(result, k, v)`` of a differential attention layer; ``shared`` (k,
    v) makes it a cross attention."""
    q = _mm("bld,dhk->blhk", u, p["q"]["kernel"], quant) + p["q"]["bias"]
    if shared is None:
        k = _mm("bld,dhk->blhk", u, p["k"]["kernel"], quant) + p["k"]["bias"]
        v = _mm("bld,dhk->blhk", u, p["v"]["kernel"], quant) + p["v"]["bias"]
    else:
        k, v = shared
    batch, length, heads, width = q.shape
    pairs, kv_pairs = heads // 2, k.shape[2] // 2
    per = pairs // kv_pairs
    window = cfg["sliding_window"] if kind == "window" else None
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"]))
           + lambda_init(at))
    # [B, L, pair, parity, w]; a pair's keys [B, L, pair, parity, w] and values [B, L, pair, 2 w] of its kv pair
    q2 = q.reshape(batch, length, pairs, 2, width)
    k2 = jnp.repeat(k.reshape(batch, length, kv_pairs, 2, width), per, axis=2)
    v2 = jnp.repeat(v.reshape(batch, length, kv_pairs, 2 * width), per, axis=2)
    size = min(length, QUERY_BLOCK)
    place = jnp.broadcast_to(jnp.arange(length)[None, :], ids.shape)

    @jax.checkpoint
    def one_block(start):
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, start, size, axis=1)  # noqa: E731
        mask = visible(cut(ids), cut(place), ids, place, window)
        scores = _mm("bqipk,bsipk->bipqs", cut(q2), k2, quant) * width ** -0.5
        probs = jax.nn.softmax(jnp.where(mask[:, None, None], scores, -1e30), axis=-1)
        probs = jnp.where(mask[:, None, None], probs, 0.0)  # a row that sees nothing (padding) attends to nothing
        return _mm("biqs,bsie->bqie", probs[:, :, 0] - lam * probs[:, :, 1], v2, quant)

    out = jax.lax.map(one_block, jnp.arange(0, length, size))  # [blocks, B, size, pairs, 2 w]
    out = jnp.moveaxis(out, 0, 1).reshape(batch, length, pairs, 2 * width)
    out = out * jax.lax.rsqrt(jnp.mean(jnp.square(out), axis=-1, keepdims=True) + SUBNORM_EPS) * p["subln"]["scale"]
    out = out * (1.0 - lambda_init(at))
    return _mm("blie,ied->bld", out, p["o"]["kernel"], quant) + p["o"]["bias"], k, v


def swiglu(u, p, quant):
    hidden = jax.nn.silu(_mm("bld,df->blf", u, p["gate"]["kernel"], quant)) * _mm(
        "bld,df->blf", u, p["up"]["kernel"], quant)
    return _mm("blf,fd->bld", hidden, p["down"]["kernel"], quant)


def layer_forward(x, carried, p, ids, cfg, index, quant=None):
    """``(x, made)``: ``carried`` what earlier layers handed on (``m``, ``k``,
    ``v``), ``made`` what this one does."""
    eps = cfg["layer_norm_eps"]
    kind, at = layer_kind(cfg, index)
    u, made = layer_norm(x, p["ln1"], eps), {}
    if kind == "mamba":
        y, memory = mamba(u, p["mamba"], ids, cfg, quant)
        if hands_on(cfg, at):
            made = {"m": memory}
    elif kind == "gmu":
        g = p["gmu"]
        y = _mm("ble,ed->bld", jax.nn.silu(_mm("bld,de->ble", u, g["in_proj"]["kernel"], quant)) * carried["m"],
                g["out_proj"]["kernel"], quant)
    else:
        shared = (carried["k"], carried["v"]) if kind == "cross" else None
        y, k, v = attention(u, p["attn"], ids, cfg, kind, at, shared, quant)
        if kind != "cross" and hands_on(cfg, at):
            made = {"k": k, "v": v}
    x = x + y
    return x + swiglu(layer_norm(x, p["ln2"], eps), p["mlp"], quant), made


def logits_of(params, tokens, ids, cfg, quant=None):
    x = params["embed"]["embedding"][tokens]
    carried = {}
    for i in range(cfg["num_hidden_layers"]):
        x, made = jax.checkpoint(lambda x, carried, p, i=i: layer_forward(x, carried, p, ids, cfg, i, quant))(
            x, carried, params["layer_{}".format(i)])
        carried = dict(carried, **made)
    x = layer_norm(x, params["ln_f"], cfg["layer_norm_eps"])
    return _mm("bld,vd->blv", x, params["embed"]["embedding"], quant)


def loss_sum(params, batch, cfg, quant=None):
    """Sum (not mean) of the valid targets' cross-entropy over the rows of
    ``batch``: rows can be processed one at a time and added."""
    tokens, seg = batch["tokens"], batch["segment_ids"]
    logits = logits_of(params, tokens[:, :-1], seg[:, :-1], cfg, quant)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    valid = ((seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] > 0)).astype(jnp.float32)
    return jnp.sum((logz - picked) * valid)


def make_grad_fn(cfg, quant=None):
    """``grad_fn(params, rows, scale) -> (loss, grads)``: ``scale`` is one
    over the whole batch's valid-target count, so rows add up to the batch's
    mean loss and its gradient."""

    def block_loss(params, rows, scale):
        with jax.default_matmul_precision("highest"):
            return loss_sum(params, rows, cfg, quant) * scale

    return jax.value_and_grad(block_loss)


def follow(cfg, key, batches, devices, quant=None):
    """Train every parameter from the seeded weights (``init_params(key,
    cfg)``) over ``batches`` (host dicts of ``[rows, L + 1]`` arrays).
    Returns each step's loss, the first step's gradient norm and sketch per
    leaf and the norm per leaf of the parameters' change over all the steps,
    without the quiet leaves (``reference/moe_lm.follow``'s rule: a leaf whose
    gradient's root mean square stayed under AdamW's ``eps`` in every step is
    one this side's optimizer does not see — here a key projection's bias,
    which moves every score of a softmax row alike; named in one printed
    line). Rows go through the gradient function one at a time on the first
    device; AdamW's moments stay on the host and come to the device a leaf at
    a time."""
    device = devices[0]
    init = jax.jit(lambda k: init_params(k, cfg))
    grad_fn = jax.jit(make_grad_fn(cfg, quant))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
    update = jax.jit(lambda p, g, m, v, count: adamw_leaf(p, g, m, v, count, cfg["optimizer"]), donate_argnums=(0, 1))
    norms, sketches = jax.jit(leaf_norms), jax.jit(leaf_sketches)

    with jax.default_device(device):
        params = init(key)
        leaves, treedef = jax.tree.flatten(params)
        moments = [(np.zeros(leaf.shape, np.float32), np.zeros(leaf.shape, np.float32)) for leaf in leaves]
        root_size = dict(zip(_names(params), (float(np.sqrt(leaf.size)) for leaf in leaves)))
        del leaves
        losses, first_grad, first_sketch, loudest = [], None, None, {}
        for step, batch in enumerate(batches):
            scale = np.float32(1.0 / max(valid_targets(batch), 1.0))
            loss, grads = 0.0, None
            for row in range(batch["tokens"].shape[0]):
                block = {k: jnp.asarray(np.asarray(v[row:row + 1])) for k, v in batch.items()}
                part, g = grad_fn(params, block, scale)
                loss += float(part)
                grads = g if grads is None else add(grads, g)
                del g
            losses.append(loss)
            grad_norms = {k: float(v) for k, v in jax.device_get(norms(grads)).items()}
            for name, norm in grad_norms.items():
                loudest[name] = max(loudest.get(name, 0.0), norm / root_size[name])
            if first_grad is None:
                first_grad = grad_norms
                first_sketch = {k: v.tolist() for k, v in jax.device_get(sketches(grads, key)).items()}
            new_leaves = []
            grad_leaves = jax.tree.leaves(grads)
            param_leaves = jax.tree.leaves(params)
            del grads, params
            for i in range(len(param_leaves)):
                p, m, v = update(param_leaves[i], grad_leaves[i], moments[i][0], moments[i][1], np.float32(step + 1))
                param_leaves[i] = grad_leaves[i] = None
                moments[i] = (np.asarray(m), np.asarray(v))
                new_leaves.append(p)
            params = jax.tree.unflatten(treedef, new_leaves)
        change = {k: float(v) for k, v in jax.device_get(norms(params, init(key))).items()}
    quiet = {name: rms for name, rms in loudest.items() if rms < cfg["optimizer"]["eps"]}
    print("reference{}: left out, gradient rms under {:g}: {}".format(
        " (" + quant + ")" if quant else "", cfg["optimizer"]["eps"],
        ", ".join("{} {:.3g}".format(name, rms) for name, rms in sorted(quiet.items())) or "none"), flush=True)

    def heard(readings):
        return {name: value for name, value in readings.items() if name not in quiet}

    return {"losses": losses, "first_grad": heard(first_grad), "first_grad_sketch": heard(first_sketch),
            "param_change": heard(change)}
