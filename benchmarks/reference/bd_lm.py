"""Plain float32 reference for the ``bd_lm`` family: a decoder of
grouped-query attention and softmax-routed SwiGLU experts (a chip's share of
them), trained by diffusion over blocks — forward pass, loss, gradients and
AdamW step in straightforward ``jax.numpy``, a dense mask written out, no
kernels, nothing imported from the program.

The layer follows ``configs/sdar-30b-a3b.json`` (d hidden, RMSNorm eps
``rms_norm_eps``, no biases), ``u = RMSNorm(x)``:

* **Attention.** ``q = u W_q`` as ``num_attention_heads`` heads of
  ``head_dim``, ``k = u W_k`` and ``v = u W_v`` as ``num_key_value_heads``;
  every head of q and of k through an RMSNorm over its ``head_dim`` with one
  learned weight; rotary positions over the whole head, halves rotated
  together (``[x1 cos - x2 sin, x2 cos + x1 sin]``), base ``rope_theta``;
  query head ``h`` reads key/value head ``h // group``; scores times
  ``head_dim ** -1/2`` under the mask below, softmax, times ``v``; ``x + W_o
  concat(heads)``.
* **Experts.** ``p = softmax(u W_r)`` over all the model's experts; the
  ``num_experts_per_tok`` largest; weights ``p_e / sum of the chosen p``. Every
  expert held here is applied to *every* position and masked by the routing —
  no sort, no gather. Experts held elsewhere add nothing. ``x + the sum``.
* Final RMSNorm and an untied head, on the noised half only.

**The objective** (block diffusion as SDAR trains it, arXiv:2510.06303 on
arXiv:2503.09573). The batch holds, per packed row of L positions, the clean
tokens, the noised tokens (a masked position holds the mask id) and the loss
weights (``1 / t`` of its block at a masked position, else 0). The model reads
``[x_0 ; x_t]``, 2 L positions, both halves at the same rotary positions. With
``b(i) = position // block_length`` counted from the document's start, query
``(half_q, i)`` sees key ``(half_k, j)`` of the same document when both are
noised and ``b(j) = b(i)``, or the key is clean and ``b(j) < b(i)`` (noised
query) or ``b(j) <= b(i)`` (clean query); padding sees nothing. Loss: ``sum_i
w_i CE(logits_t[i], x_0[i])`` over the noised half, no shift, over the
batch's real tokens.

Departures from the published description (each under ``assumed`` in the
configuration): block length, schedule and mask id (the config gives none);
the routers' matrices as :func:`calibrated_routers` leaves them.

``quant="fp8"`` is the control: every matrix product takes its operands
rounded to float8. Memory as ``reference/moe_lm``: AdamW's moments on the host,
the update leaf by leaf, rows one at a time, queries a block at a time.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.control import fake_quant
from benchmarks.reference.moe_lm import _names, adamw_leaf, leaf_norms, leaf_sketches

QUERY_BLOCK = 512


def leaf_shapes(cfg):
    """``{path: (shape, init)}``: ``init`` a normal's std, or ``("const", value)``."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv_heads, width = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    held, ff = cfg["experts_held"][1], cfg["moe_intermediate_size"]
    shapes = {("embed", "embedding"): ((v, d), 1.0)}
    for i in range(cfg["num_hidden_layers"]):
        layer = "layer_{}".format(i)
        shapes[(layer, "ln1", "scale")] = ((d,), ("const", 1.0))
        shapes[(layer, "attn", "q", "kernel")] = ((d, heads, width), d ** -0.5)
        shapes[(layer, "attn", "k", "kernel")] = ((d, kv_heads, width), d ** -0.5)
        shapes[(layer, "attn", "v", "kernel")] = ((d, kv_heads, width), d ** -0.5)
        shapes[(layer, "attn", "q_norm", "scale")] = ((width,), ("const", 1.0))
        shapes[(layer, "attn", "k_norm", "scale")] = ((width,), ("const", 1.0))
        shapes[(layer, "attn", "o", "kernel")] = ((heads, width, d), (heads * width) ** -0.5)
        shapes[(layer, "ln2", "scale")] = ((d,), ("const", 1.0))
        shapes[(layer, "moe", "router")] = ((d, cfg["router_experts"]), d ** -0.5)
        shapes[(layer, "moe", "experts_gate")] = ((held, d, ff), d ** -0.5)
        shapes[(layer, "moe", "experts_up")] = ((held, d, ff), d ** -0.5)
        shapes[(layer, "moe", "experts_down")] = ((held, ff, d), ff ** -0.5)
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


def _rope(x, positions, theta):
    """``x`` ``[B, L, H, D]``: the halves rotated together."""
    half = x.shape[-1] // 2
    freqs = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, :, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(angles) - x2 * jnp.sin(angles), x2 * jnp.cos(angles) + x1 * jnp.sin(angles)], -1)


def doubled(batch, cfg):
    """The row as the model reads it: ``(tokens, positions, ids, block, noised)``,
    each ``[B, 2 L]``: the clean copy, then the noised one."""
    twice = lambda x: jnp.concatenate([x, x], axis=1)  # noqa: E731
    tokens = jnp.concatenate([batch["tokens"], batch["noised_tokens"]], axis=1)
    noised = jnp.concatenate([jnp.zeros_like(batch["tokens"]), jnp.ones_like(batch["tokens"])], axis=1)
    return tokens, twice(batch["positions"]), twice(batch["segment_ids"]), twice(batch["positions"] // cfg["block_length"]), noised


def visible(queries, keys):
    """The mask written out, ``bool [B, queries, keys]``; each side is its
    positions' ``(ids, block, noised)``, ``[B, n]`` each."""
    (q_ids, q_block, q_noised), (k_ids, k_block, k_noised) = (
        tuple(t[:, :, None] for t in queries), tuple(t[:, None, :] for t in keys))
    same = (q_ids == k_ids) & (q_ids > 0)
    among_noised = (q_noised == 1) & (k_noised == 1) & (q_block == k_block)
    clean = (k_noised == 0) & jnp.where(q_noised == 1, k_block < q_block, k_block <= q_block)
    return same & (among_noised | clean)


def attention(x, p, positions, ids, block, noised, cfg, quant=None):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = _rope(_rms(_mm("bld,dhk->blhk", x, p["q"]["kernel"], quant), p["q_norm"]["scale"], eps), positions, theta)
    k = _rope(_rms(_mm("bld,dhk->blhk", x, p["k"]["kernel"], quant), p["k_norm"]["scale"], eps), positions, theta)
    v = _mm("bld,dhk->blhk", x, p["v"]["kernel"], quant)
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)  # query head h reads head h // group
    length = x.shape[1]
    size = min(length, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(start):
        at = lambda t: jax.lax.dynamic_slice_in_dim(t, start, size, axis=1)  # noqa: E731
        q_block, mask = at(q), visible((at(ids), at(block), at(noised)), (ids, block, noised))
        scores = _mm("bqhk,bshk->bhqs", q_block, k, quant) * q.shape[-1] ** -0.5
        probs = jax.nn.softmax(jnp.where(mask[:, None], scores, -1e30), axis=-1)
        return _mm("bhqs,bshk->bqhk", probs, v, quant)

    out = jax.lax.map(one_block, jnp.arange(0, length, size))  # [blocks, B, size, H, D]
    out = jnp.moveaxis(out, 0, 1).reshape(x.shape[0], length, out.shape[-2], out.shape[-1])
    return _mm("blhk,hkd->bld", out, p["o"]["kernel"], quant)


def swiglu(x, gate, up, down, quant):
    hidden = jax.nn.silu(_mm("td,df->tf", x, gate, quant)) * _mm("td,df->tf", x, up, quant)
    return _mm("tf,fd->td", hidden, down, quant)


def routing(x, router, cfg, quant=None):
    """``[T, E]`` weight of every expert of the model for every position: 0
    where the expert was not chosen."""
    probs = jax.nn.softmax(_mm("td,de->te", x, router, quant), axis=-1)
    _, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    picked = probs * jnp.sum(jax.nn.one_hot(chosen, probs.shape[-1], dtype=probs.dtype), axis=1)
    return picked / jnp.sum(picked, axis=-1, keepdims=True)


def experts(x, p, cfg, quant=None, held=None):
    """The held experts' weighted outputs on ``x`` ``[B, L, d]`` (``held``:
    first, count; default the configuration's)."""
    first, count = held if held is not None else cfg["experts_held"]
    flat = x.reshape(-1, x.shape[-1])
    weights = routing(flat, p["router"], cfg, quant)
    out = jnp.zeros_like(flat)
    for e in range(count):
        y = swiglu(flat, p["experts_gate"][e], p["experts_up"][e], p["experts_down"][e], quant)
        out = out + weights[:, first + e, None] * y
    return out.reshape(x.shape)


def layer_forward(x, p, positions, ids, block, noised, cfg, quant=None, routed=experts):
    eps = cfg["rms_norm_eps"]
    x = x + attention(_rms(x, p["ln1"]["scale"], eps), p["attn"], positions, ids, block, noised, cfg, quant)
    return x + routed(_rms(x, p["ln2"]["scale"], eps), p["moe"], cfg, quant)


def logits_of(params, batch, cfg, quant=None):
    """The noised half's logits, ``[B, L, V]``."""
    tokens, positions, ids, block, noised = doubled(batch, cfg)
    x = params["embed"]["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda x, p: layer_forward(x, p, positions, ids, block, noised, cfg, quant))(
            x, params["layer_{}".format(i)])
    x = _rms(x[:, batch["tokens"].shape[1]:], params["ln_f"]["scale"], cfg["rms_norm_eps"])
    return _mm("bld,dv->blv", x, params["lm_head"]["kernel"], quant)


#: halvings of the interval in which :func:`calibrated_routers` looks for a layer's offset
CALIBRATE_ROUNDS, CALIBRATE_SPAN = 40, 16.0


def calibrated_routers(key, cfg, batch):
    """``{layer: W_r}`` for the weights of ``key``: every layer's seeded
    router with one number added to the logits of the experts held here, so
    that on ``batch`` they receive their even share of the slots, layer by
    layer in one forward pass (each layer routes with its calibrated router
    before the next is looked at; a layer aims at what brings the sum over the
    layers so far to its even share).

    A published model of this family was trained with a load-balancing loss:
    an expert-parallel rank of it receives about its share, 1 / 8 of the slots
    here. Seeded routers do not: every noised row is half the one mask token,
    whose copies all go where its embedding sends them (at the cell's size
    every masked position of a layer picks the same 8 experts of 128, a
    quarter of the layer's positions), and whether those are among the 16
    held here is the seed's luck: 10.6-13.2% of the first batch's slots over
    four seeds. The rate follows the held share (0.45-0.55% a point of it).
    The number is folded into the matrix along the positions' mean direction
    ``m`` (``W_r[:, held] += c m / |m|^2``: every position's logit moves by
    about ``c``), so the layer stays the architecture's: a matrix and no
    bias. It holds for as long as the weights stay near the seeded ones: the
    cell's optimizer runs at a warm-up's rate (``PERF.md`` section 6, PR 33)."""
    params = init_params(key, cfg)
    tokens, positions, ids, block, noised = doubled(batch, cfg)
    first, count = cfg["experts_held"]
    k, experts_all = cfg["num_experts_per_tok"], cfg["router_experts"]
    held = ((jnp.arange(experts_all) >= first) & (jnp.arange(experts_all) < first + count)).astype(jnp.float32)
    found, held_so_far = {}, jnp.float32(0.0)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens]
        for i in range(cfg["num_hidden_layers"]):
            layer = "layer_{}".format(i)
            even = x.shape[0] * x.shape[1] * k * count / experts_all
            target = (i + 1) * even - held_so_far

            def routed(h, p, cfg, quant, layer=layer, target=target):
                flat = h.reshape(-1, h.shape[-1])
                mean = jnp.mean(flat, axis=0)
                toward = (mean / jnp.sum(mean * mean))[:, None] * held[None, :]

                def slots(c):
                    _, chosen = jax.lax.top_k(flat @ (p["router"] + c * toward), k)
                    return jnp.sum(held[chosen])

                def halve(_, bounds):
                    low, high = bounds
                    middle = 0.5 * (low + high)
                    above = slots(middle) > target
                    return jnp.where(above, low, middle), jnp.where(above, middle, high)

                low, high = jax.lax.fori_loop(0, CALIBRATE_ROUNDS, halve, (-CALIBRATE_SPAN, CALIBRATE_SPAN))
                found[layer] = p["router"] + 0.5 * (low + high) * toward
                found[layer + "/slots"] = slots(0.5 * (low + high))
                return experts(h, dict(p, router=found[layer]), cfg, quant)

            x = layer_forward(x, params[layer], positions, ids, block, noised, cfg, routed=routed)
            held_so_far = held_so_far + found.pop(layer + "/slots")
    return found


def loss_sum(params, batch, cfg, quant=None):
    """Sum (not mean) of the weighted cross-entropy over the rows of
    ``batch``: rows can be processed one at a time and added."""
    logits = logits_of(params, batch, cfg, quant)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, batch["tokens"][..., None], axis=-1)[..., 0]
    return jnp.sum((logz - picked) * batch["loss_weights"])


def real_tokens(batch):
    return float((np.asarray(batch["segment_ids"]) > 0).sum())


def make_grad_fn(cfg, quant=None):
    """``grad_fn(params, rows, scale) -> (loss, grads)``: ``scale`` is one
    over the whole batch's real tokens, so rows add up to the batch's loss
    and its gradient."""

    def block_loss(params, rows, scale):
        with jax.default_matmul_precision("highest"):
            return loss_sum(params, rows, cfg, quant) * scale

    return jax.value_and_grad(block_loss)


def follow(cfg, key, batches, devices, quant=None, routers=None):
    """Train from the seeded weights (``init_params(key, cfg, routers)``),
    all but the parameters the configuration's ``optimizer.frozen`` names,
    over ``batches`` (host dicts of ``[rows, L]`` arrays). Returns each
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
            scale = np.float32(1.0 / max(real_tokens(batch), 1.0))
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
