"""Plain float32 reference for the ``swa_lm`` family: a decoder whose layers
are full or sliding-window grouped-query attention, of a head count and a
rotary kind by the layer's type, with a sigmoid gate a head, over a dense
SwiGLU or softmax-routed SwiGLU experts (a chip's share of them) beside a
shared one — forward pass, next-token loss, gradients and AdamW step in
straightforward ``jax.numpy``, masks written out, no kernels, nothing imported
from the program.

The layer follows ``configs/laguna-s-2-1.json`` (d hidden, RMSNorm eps
``rms_norm_eps``, no biases, one residual stream), ``u = RMSNorm(x)``:

* **Attention** of layer ``l``. ``H = num_attention_heads_per_layer[l]`` query
  heads and ``num_key_value_heads`` key/value heads of ``head_dim``: ``q = u
  W_q``, ``k = u W_k``, ``v = u W_v``; query head ``h`` reads key/value head
  ``h // (H / kv heads)``. Rotary positions by ``rope_parameters[layer_types[l]]``:
  the first ``partial_rotary_factor`` of the head, its halves rotated together
  (``[x1 cos - x2 sin, x2 cos + x1 sin]``), the rest unrotated; frequencies
  ``rope_theta ** (-2i / rotated)``, under ``rope_type: yarn`` the slow ones
  divided by ``factor``, blended over the correction range (``beta_fast`` to
  ``beta_slow`` rotations over ``original_max_position_embeddings``), cos and
  sin times ``attention_factor``. Scores ``q . k / sqrt(head_dim)``; key ``j``
  is visible to query ``i`` when both are of one document (the same non-zero
  segment id), ``j <= i`` and, on a ``sliding_attention`` layer, ``i - j <
  sliding_window``; softmax; ``a_h`` the head's output. ``g = sigmoid(u W_g)``
  (``W_g`` ``[d, H]``); ``x + sum_h g_h a_h W_o[h]``.
* **Feed-forward.** Layer 0 (``mlp_layer_types[l] == "dense"``): SwiGLU
  ``intermediate_size`` wide. Else ``p = softmax(u W_r)`` over all the model's
  experts; the ``num_experts_per_tok`` largest; ``w_e = p_e / sum of the
  chosen p``; ``moe_routed_scaling_factor * sum over the chosen experts held
  here of w_e SwiGLU_e(u)`` ``+ SwiGLU_shared(u)``. Every expert held here is
  applied to *every* position and masked by the routing: no sort, no gather,
  nothing dropped. Experts held elsewhere add nothing.
* Final RMSNorm, an untied head, next-token cross-entropy over the valid
  targets (a target is valid when it and the position it is predicted from
  share a real document).

Departures from the published description (each under ``assumed`` in the
configuration): the window counts the query itself; softmax scoring with no
bias and no auxiliary loss; the shared expert ungated; no norm on the heads
of q and k; the gate reads the sub-layer's normed input; the routers' matrices
as :func:`calibrated_routers` leaves them.

``quant="fp8"`` is the control: every matrix product takes its operands
rounded to float8. Memory as ``reference/moe_lm``: AdamW's moments on the host,
the update leaf by leaf, rows one at a time, queries a block at a time.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.control import fake_quant
from benchmarks.reference.moe_lm import _names, adamw_leaf, leaf_norms, leaf_sketches, valid_targets

QUERY_BLOCK = 512


def layer_kinds(cfg, index):
    """``(windowed, heads, dense)`` of layer ``index``."""
    windowed = cfg["layer_types"][index] == "sliding_attention"
    return windowed, cfg["num_attention_heads_per_layer"][index], cfg["mlp_layer_types"][index] == "dense"


def leaf_shapes(cfg):
    """``{path: (shape, init)}``: ``init`` a normal's std, or ``("const", value)``."""
    d, v, width, kv_heads = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    held, ff, shared = cfg["experts_held"][1], cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    shapes = {("embed", "embedding"): ((v, d), 1.0)}
    for i in range(cfg["num_hidden_layers"]):
        layer = "layer_{}".format(i)
        _, heads, dense = layer_kinds(cfg, i)
        shapes[(layer, "ln1", "scale")] = ((d,), ("const", 1.0))
        shapes[(layer, "attn", "q", "kernel")] = ((d, heads, width), d ** -0.5)
        shapes[(layer, "attn", "k", "kernel")] = ((d, kv_heads, width), d ** -0.5)
        shapes[(layer, "attn", "v", "kernel")] = ((d, kv_heads, width), d ** -0.5)
        shapes[(layer, "attn", "gate", "kernel")] = ((d, heads), d ** -0.5)
        shapes[(layer, "attn", "o", "kernel")] = ((heads, width, d), (heads * width) ** -0.5)
        shapes[(layer, "ln2", "scale")] = ((d,), ("const", 1.0))
        if dense:
            wide = cfg["intermediate_size"]
            shapes[(layer, "mlp", "gate", "kernel")] = ((d, wide), d ** -0.5)
            shapes[(layer, "mlp", "up", "kernel")] = ((d, wide), d ** -0.5)
            shapes[(layer, "mlp", "down", "kernel")] = ((wide, d), wide ** -0.5)
            continue
        shapes[(layer, "moe", "router")] = ((d, cfg["router_experts"]), d ** -0.5)
        shapes[(layer, "moe", "experts_gate")] = ((held, d, ff), d ** -0.5)
        shapes[(layer, "moe", "experts_up")] = ((held, d, ff), d ** -0.5)
        shapes[(layer, "moe", "experts_down")] = ((held, ff, d), ff ** -0.5)
        shapes[(layer, "moe", "shared", "gate", "kernel")] = ((d, shared), d ** -0.5)
        shapes[(layer, "moe", "shared", "up", "kernel")] = ((d, shared), d ** -0.5)
        shapes[(layer, "moe", "shared", "down", "kernel")] = ((shared, d), shared ** -0.5)
    shapes[("ln_f", "scale")] = ((d,), ("const", 1.0))
    shapes[("lm_head", "kernel")] = ((d, v), d ** -0.5)
    return shapes


def init_params(key, cfg, routers=None):
    """Seeded float32 weights as a nested dict, named as the program's model
    names its parameters. ``routers`` (``{layer: [d, E]}``, what
    :func:`calibrated_routers` returned for the same key) takes the place of
    the seeded routers' matrices."""
    tree = {}
    for index, (path, (shape, init)) in enumerate(leaf_shapes(cfg).items()):
        if isinstance(init, tuple):
            leaf = init[1] * jnp.ones(shape, jnp.float32)
        else:
            leaf = init * jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaf
    for layer, router in (routers or {}).items():
        tree[layer]["moe"]["router"] = jnp.asarray(router, jnp.float32)
    return tree


def parameter_count(cfg):
    return sum(int(np.prod(shape)) for shape, _ in leaf_shapes(cfg).values())


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, fake_quant(a, quant), fake_quant(b, quant))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def inv_freq(rope, rotated):
    """The ``rotated // 2`` inverse frequencies of one layer type's rotary
    positions (``rope``: its entry of ``rope_parameters``)."""
    kept = float(rope["rope_theta"]) ** (-jnp.arange(0, rotated, 2, dtype=jnp.float32) / rotated)
    if rope["rope_type"] != "yarn":
        return kept

    def dim_of(rotations):
        return rotated * math.log(rope["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (
            2 * math.log(rope["rope_theta"]))

    low, high = max(math.floor(dim_of(rope["beta_fast"])), 0), min(math.ceil(dim_of(rope["beta_slow"])), rotated - 1)
    ramp = jnp.clip((jnp.arange(rotated // 2, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
    return kept / rope["factor"] * ramp + kept * (1.0 - ramp)


def _rope(x, positions, rope):
    """``x`` ``[B, L, H, D]``: the first ``partial_rotary_factor`` of the head
    rotated, its halves together; the rest as it is."""
    rotated = int(x.shape[-1] * rope["partial_rotary_factor"])
    half = rotated // 2
    angles = positions.astype(jnp.float32)[:, :, None, None] * inv_freq(rope, rotated)
    scale = rope["attention_factor"] if rope["rope_type"] == "yarn" else 1.0
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    x1, x2 = x[..., :half], x[..., half:rotated]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotated:]], -1)


def visible(q_ids, q_at, k_ids, k_at, window):
    """The mask written out, ``bool [B, queries, keys]``: ids and places in
    the row of each side, ``[B, n]`` each; ``window`` None on a full layer."""
    behind = q_at[:, :, None] - k_at[:, None, :]
    seen = (q_ids[:, :, None] == k_ids[:, None, :]) & (q_ids[:, :, None] > 0) & (behind >= 0)
    return seen if window is None else seen & (behind < window)


def attention(x, p, positions, ids, cfg, index, quant=None):
    """Layer ``index``'s attention on ``x`` ``[B, L, d]``, the output
    projection included."""
    windowed, _, _ = layer_kinds(cfg, index)
    rope = cfg["rope_parameters"][cfg["layer_types"][index]]
    window = cfg["sliding_window"] if windowed else None
    q = _rope(_mm("bld,dhk->blhk", x, p["q"]["kernel"], quant), positions, rope)
    k = _rope(_mm("bld,dhk->blhk", x, p["k"]["kernel"], quant), positions, rope)
    v = _mm("bld,dhk->blhk", x, p["v"]["kernel"], quant)
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)  # query head h reads head h // group
    gate = jax.nn.sigmoid(_mm("bld,dh->blh", x, p["gate"]["kernel"], quant))
    length = x.shape[1]
    size = min(length, QUERY_BLOCK)
    at = jnp.broadcast_to(jnp.arange(length)[None, :], ids.shape)

    @jax.checkpoint
    def one_block(start):
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, start, size, axis=1)  # noqa: E731
        mask = visible(cut(ids), cut(at), ids, at, window)
        scores = _mm("bqhk,bshk->bhqs", cut(q), k, quant) * q.shape[-1] ** -0.5
        probs = jax.nn.softmax(jnp.where(mask[:, None], scores, -1e30), axis=-1)
        return _mm("bhqs,bshk->bqhk", probs, v, quant)

    out = jax.lax.map(one_block, jnp.arange(0, length, size))  # [blocks, B, size, H, D]
    out = jnp.moveaxis(out, 0, 1).reshape(x.shape[0], length, out.shape[-2], out.shape[-1])
    return _mm("blhk,hkd->bld", out * gate[..., None], p["o"]["kernel"], quant)


def swiglu(x, gate, up, down, quant):
    hidden = jax.nn.silu(_mm("td,df->tf", x, gate, quant)) * _mm("td,df->tf", x, up, quant)
    return _mm("tf,fd->td", hidden, down, quant)


def routing(x, router, cfg, quant=None):
    """``[T, E]`` weight of every expert of the model for every position: 0
    where the expert was not chosen; the scaling factor included."""
    probs = jax.nn.softmax(_mm("td,de->te", x, router, quant), axis=-1)
    _, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    picked = probs * jnp.sum(jax.nn.one_hot(chosen, probs.shape[-1], dtype=probs.dtype), axis=1)
    return cfg["moe_routed_scaling_factor"] * picked / jnp.sum(picked, axis=-1, keepdims=True)


def experts(x, p, cfg, quant=None, held=None, shared=True):
    """The held experts' weighted outputs on ``x`` ``[B, L, d]`` (``held``:
    first, count; default the configuration's) plus, with ``shared``, the
    shared expert's."""
    first, count = held if held is not None else cfg["experts_held"]
    flat = x.reshape(-1, x.shape[-1])
    weights = routing(flat, p["router"], cfg, quant)
    out = jnp.zeros_like(flat)
    for e in range(count):
        y = swiglu(flat, p["experts_gate"][e], p["experts_up"][e], p["experts_down"][e], quant)
        out = out + weights[:, first + e, None] * y
    if shared:
        s = p["shared"]
        out = out + swiglu(flat, s["gate"]["kernel"], s["up"]["kernel"], s["down"]["kernel"], quant)
    return out.reshape(x.shape)


def layer_forward(x, p, positions, ids, cfg, index, quant=None, routed=experts):
    eps = cfg["rms_norm_eps"]
    x = x + attention(_rms(x, p["ln1"]["scale"], eps), p["attn"], positions, ids, cfg, index, quant)
    u = _rms(x, p["ln2"]["scale"], eps)
    if layer_kinds(cfg, index)[2]:
        m = p["mlp"]
        y = swiglu(u.reshape(-1, u.shape[-1]), m["gate"]["kernel"], m["up"]["kernel"], m["down"]["kernel"], quant)
        return x + y.reshape(x.shape)
    return x + routed(u, p["moe"], cfg, quant)


def logits_of(params, tokens, positions, ids, cfg, quant=None):
    x = params["embed"]["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda x, p, i=i: layer_forward(x, p, positions, ids, cfg, i, quant))(
            x, params["layer_{}".format(i)])
    x = _rms(x, params["ln_f"]["scale"], cfg["rms_norm_eps"])
    return _mm("bld,dv->blv", x, params["lm_head"]["kernel"], quant)


#: halvings of the interval in which :func:`calibrated_routers` looks for a layer's offset
CALIBRATE_ROUNDS, CALIBRATE_SPAN = 40, 16.0


def calibrated_routers(key, cfg, batch):
    """``{layer: W_r}`` for the weights of ``key``: every routed layer's
    seeded router with one number added to the logits of the experts held
    here, so that on ``batch`` they receive their even share of the real
    positions' slots (``held / router_experts``: 3.125% for 8 of 256;
    padding, segment id 0, takes no slot in the program: the
    configuration's ``padding_slots``), layer by layer in one
    forward pass (each layer routes with its calibrated router before the next
    is looked at; a layer aims at what brings the sum over the layers so far
    to its even share).

    A published model of this family was trained to a balanced load: an
    expert-parallel rank of it receives about its share. Seeded routers do
    not give one: which 10 of 256 a position picks follows the seed, and the
    8 held here may receive twice their share or half of it; a routed layer's
    work, and the cell's rate, follow the held share (``reference/bd_lm`` has
    the readings that led to this). The number is folded into the matrix
    along the positions' mean direction ``m`` (``W_r[:, held] += c m /
    |m|^2``: every position's logit moves by about ``c``), so the layer stays
    the architecture's: a matrix and no bias; the configuration's
    ``optimizer.frozen`` then leaves the routers' matrices where they are."""
    params = init_params(key, cfg)
    tokens, positions, ids = (batch[name][:, :-1] for name in ("tokens", "positions", "segment_ids"))
    first, count = cfg["experts_held"]
    k, experts_all = cfg["num_experts_per_tok"], cfg["router_experts"]
    held = ((jnp.arange(experts_all) >= first) & (jnp.arange(experts_all) < first + count)).astype(jnp.float32)
    real = (ids > 0).reshape(-1, 1).astype(jnp.float32)
    found, held_so_far, routed_layers = {}, jnp.float32(0.0), 0
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens]
        for i in range(cfg["num_hidden_layers"]):
            layer = "layer_{}".format(i)
            routed_layers += not layer_kinds(cfg, i)[2]
            even = jnp.sum(real) * k * count / experts_all
            target = routed_layers * even - held_so_far

            def routed(h, p, cfg, quant, layer=layer, target=target):
                flat = h.reshape(-1, h.shape[-1])
                mean = jnp.mean(flat, axis=0)
                toward = (mean / jnp.sum(mean * mean))[:, None] * held[None, :]

                def slots(c):
                    _, chosen = jax.lax.top_k(flat @ (p["router"] + c * toward), k)
                    return jnp.sum(held[chosen] * real)

                def halve(_, bounds):
                    low, high = bounds
                    middle = 0.5 * (low + high)
                    above = slots(middle) > target
                    return jnp.where(above, low, middle), jnp.where(above, middle, high)

                low, high = jax.lax.fori_loop(0, CALIBRATE_ROUNDS, halve, (-CALIBRATE_SPAN, CALIBRATE_SPAN))
                found[layer] = p["router"] + 0.5 * (low + high) * toward
                found[layer + "/slots"] = slots(0.5 * (low + high))
                return experts(h, dict(p, router=found[layer]), cfg, quant)

            x = layer_forward(x, params[layer], positions, ids, cfg, i, routed=routed)
            held_so_far = held_so_far + found.pop(layer + "/slots", 0.0)
    return found


def loss_sum(params, batch, cfg, quant=None):
    """Sum (not mean) of the valid targets' cross-entropy over the rows of
    ``batch``: rows can be processed one at a time and added."""
    tokens, seg, pos = batch["tokens"], batch["segment_ids"], batch["positions"]
    logits = logits_of(params, tokens[:, :-1], pos[:, :-1], seg[:, :-1], cfg, quant)
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


def follow(cfg, key, batches, devices, quant=None, routers=None):
    """Train from the seeded weights (``init_params(key, cfg, routers)``),
    all but the parameters the configuration's ``optimizer.frozen`` names,
    over ``batches`` (host dicts of ``[rows, L + 1]`` arrays). Returns each
    step's loss, the first step's gradient norm and sketch per leaf and the
    norm per leaf of the parameters' change over all the steps, without the
    quiet leaves (``reference/moe_lm.follow``'s rule: a leaf whose gradient's
    root mean square stayed under AdamW's ``eps`` in every step is one this
    side's optimizer does not see; named in one printed line). Rows go
    through the gradient function one at a time on the first device; AdamW's
    moments stay on the host and come to the device a leaf at a time."""
    device = devices[0]
    init = jax.jit(lambda k, r: init_params(k, cfg, r))
    grad_fn = jax.jit(make_grad_fn(cfg, quant))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
    update = jax.jit(lambda p, g, m, v, count: adamw_leaf(p, g, m, v, count, cfg["optimizer"]), donate_argnums=(0, 1))
    norms, sketches = jax.jit(leaf_norms), jax.jit(leaf_sketches)

    with jax.default_device(device):
        params = init(key, routers)
        frozen = [any(name.endswith(end) for end in cfg["optimizer"]["frozen"]) for name in _names(params)]
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
                if frozen[i]:  # the optimizer leaves it where it is
                    new_leaves.append(param_leaves[i])
                    continue
                p, m, v = update(param_leaves[i], grad_leaves[i], moments[i][0], moments[i][1], np.float32(step + 1))
                param_leaves[i] = grad_leaves[i] = None
                moments[i] = (np.asarray(m), np.asarray(v))
                new_leaves.append(p)
            params = jax.tree.unflatten(treedef, new_leaves)
        change = {k: float(v) for k, v in jax.device_get(norms(params, init(key, routers))).items()}
    quiet = {name: rms for name, rms in loudest.items() if rms < cfg["optimizer"]["eps"]}
    print("reference{}: left out, gradient rms under {:g}: {}".format(
        " (" + quant + ")" if quant else "", cfg["optimizer"]["eps"],
        ", ".join("{} {:.3g}".format(name, rms) for name, rms in sorted(quiet.items())) or "none"), flush=True)

    def heard(readings):
        return {name: value for name, value in readings.items() if name not in quiet}

    return {"losses": losses, "first_grad": heard(first_grad), "first_grad_sketch": heard(first_sketch),
            "param_change": heard(change)}
