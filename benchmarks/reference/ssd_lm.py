"""Plain float32 reference for the ``ssd_lm`` family: the one-sub-layer hybrid
of ``configs/nemotron-3-super.json`` (``model_type`` ``nemotron_h``) — Mamba-2
blocks, grouped-query attention blocks and latent mixture-of-experts blocks, a
chip's share of each — forward pass, next-token loss, gradients and AdamW step
in straightforward ``jax.numpy``: the recurrence a ``lax.scan`` over
positions, masks written out, the experts a loop over the held ones, no
kernels, nothing imported from the program.

``d`` hidden, ``u = RMSNorm(x)`` (a learned weight, eps ``norm_eps``); **a
block is one sub-layer**, ``x <- x + F(u)``; ``hybrid_override_pattern[first_layer
+ i]`` says which ``F`` block ``i`` holds (:func:`block_kind`). No positional
encoding anywhere.

* **``M``, Mamba-2.** ``H`` heads of ``P`` channels in ``G`` groups, ``N``
  states, as many as the parameters hold (the chip's share: whole groups).
  ``[z | xBC | dt] = u W_in`` (widths ``H P``, ``H P + 2 G N``, ``H``); ``xBC_t
  = SiLU(b + sum_{j<taps} w_j * xBC_{t-j})`` per channel, a term of another
  document or from before the row's start zero; split into ``x [H, P]``, ``B``,
  ``C [G, N]``, head ``h`` reading group ``h // (H / G)``; ``Delta = softplus(dt
  + b_dt)`` a head; ``a = -exp(A_log)`` one scalar a head; ``h_t = exp(Delta_t
  a) h_{t-1} + (Delta_t x_t) (x) B_t`` (``[P, N]`` a head), ``h = 0`` before each
  document's first position; ``y_t = h_t C_t + D x_t``; the gated norm, gate
  first, over each group's ``H P / G`` channels: ``RMSNorm_group(y * SiLU(z))``
  with a learned weight; result ``y W_out``.
* **``*``, attention.** ``q, k, v = u W`` (``H``, ``K``, ``K`` heads of ``w``,
  the share's), no bias, no head norm, no rotary; head ``h`` reads key/value
  head ``h // (H / K)``; ``softmax(q k^T / sqrt(w))`` over the keys of the same
  non-zero document not after the query; ``concat_h(o_h) W_o``.
* **``E``, latent mixture of experts.** ``s = sigmoid(u W_r)`` over all the
  model's experts; chosen: top-k of ``s + b``; weights ``s`` at the chosen over
  their sum (+ 1e-20) times ``routed_scaling_factor``. ``l = u W_down`` (``d`` to
  the latent); every expert held here applied to *every* token and masked by
  the routing, ``f_e(l) = relu(l W_e^up)^2 W_e^down``; ``r = (sum_e w_e f_e(l))
  W_up``; the shared expert on ``u`` itself, ``relu(u W_s^up)^2 W_s^down``;
  result ``r + shared(u)``. Experts held elsewhere add nothing.
* Final RMSNorm; logits ``RMSNorm(x) W_head`` (untied); next-token
  cross-entropy over the valid targets (a target is valid when it and the
  position it is predicted from share a real document).

A share's partial sums (``W_out``'s and ``W_o``'s over the held heads, the held
experts' terms) go on to the next block as they are: what the absent heads and
experts would add is left out, here and in the program alike.
:func:`share_params` cuts a whole block's parameters to one share's, for the
test that adds the shares up.

Departures from the published description are under ``assumed`` in the
configuration. ``quant="fp8"`` is the control: every matrix product takes its
operands rounded to float8 (the recurrence and the convolution have none and
stay float32). Memory: rows one at a time, queries a block at a time, the scan
in blocks of :data:`HEAD_BLOCK` heads, every block recomputed in the backward
pass; AdamW's moments on the host, the update leaf by leaf.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.control import fake_quant
from benchmarks.reference.moe_lm import (
    BALANCE_ROUNDS, BALANCE_STEPS, _names, adamw_leaf, leaf_norms, leaf_sketches, router_scores, routing,
    valid_targets)
from benchmarks.reference.ssm_lm import conv, visible

QUERY_BLOCK = 512
#: heads a ``lax.scan`` carries at once: its backward keeps a state a position (8 x 64 x 128 float32 x 8192 = 2.1 GB)
HEAD_BLOCK = 8

KINDS = {"M": "mamba2", "*": "attn", "E": "moe"}


def block_kind(cfg, index):
    """``mamba2``, ``attn`` or ``moe``: what the ``index``-th block held is."""
    return KINDS[cfg["hybrid_override_pattern"][cfg.get("first_layer", 0) + index]]


def shares(cfg):
    """``(index, shares)`` of the heads held here."""
    return tuple(cfg.get("heads_held") or (0, 1))


def leaf_shapes(cfg):
    """``{path: (shape, init)}``: ``init`` a normal's std, ``("const",
    value)``, ``"a_log"`` (``log`` of the head's place in the model, from 1)
    or ``"dt_bias"`` (the inverse softplus of a step log-uniform in
    [``time_step_min``, ``time_step_max``], floored). The output projections
    of a share of the heads are seeded at the whole model's fan-in."""
    d, v, of = cfg["hidden_size"], cfg["vocab_size"], shares(cfg)[1]
    heads, groups, width, states = cfg["mamba_num_heads"], cfg["n_groups"], cfg["mamba_head_dim"], cfg["ssm_state_size"]
    inner, taps = heads * width, cfg["conv_kernel"]
    q_heads, kv_heads, head = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    held, latent, wide = cfg["experts_held"][1], cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    shared = cfg["moe_shared_expert_intermediate_size"]
    shapes = {("embed", "embedding"): ((v, d), 1.0)}
    for i in range(cfg["num_hidden_layers"]):
        layer, kind = "layer_{}".format(i), block_kind(cfg, i)
        if kind == "mamba2":
            shapes[(layer, "ln1", "scale")] = ((d,), ("const", 1.0))
            shapes[(layer, "mamba2", "in_proj", "kernel")] = ((d, 2 * inner + 2 * groups * states + heads), d ** -0.5)
            shapes[(layer, "mamba2", "conv_kernel")] = ((taps, inner + 2 * groups * states), taps ** -0.5)
            shapes[(layer, "mamba2", "conv_bias")] = ((inner + 2 * groups * states,), ("const", 0.0))
            shapes[(layer, "mamba2", "dt_bias")] = ((heads,), "dt_bias")
            shapes[(layer, "mamba2", "a_log")] = ((heads,), "a_log")
            shapes[(layer, "mamba2", "skip")] = ((heads,), ("const", 1.0))
            shapes[(layer, "mamba2", "norm_scale")] = ((inner,), ("const", 1.0))
            shapes[(layer, "mamba2", "out_proj", "kernel")] = ((inner, d), (inner * of) ** -0.5)
        elif kind == "attn":
            shapes[(layer, "ln1", "scale")] = ((d,), ("const", 1.0))
            for name, n in (("q", q_heads), ("k", kv_heads), ("v", kv_heads)):
                shapes[(layer, "attn", name, "kernel")] = ((d, n, head), d ** -0.5)
            shapes[(layer, "attn", "o", "kernel")] = ((q_heads, head, d), (q_heads * of * head) ** -0.5)
        else:
            shapes[(layer, "ln2", "scale")] = ((d,), ("const", 1.0))
            shapes[(layer, "moe", "router")] = ((d, cfg["router_experts"]), d ** -0.5)
            shapes[(layer, "moe", "router_bias")] = ((cfg["router_experts"],), 0.02)
            shapes[(layer, "moe", "latent_down", "kernel")] = ((d, latent), d ** -0.5)
            shapes[(layer, "moe", "experts_up")] = ((held, latent, wide), latent ** -0.5)
            shapes[(layer, "moe", "experts_down")] = ((held, wide, latent), wide ** -0.5)
            shapes[(layer, "moe", "latent_up", "kernel")] = ((latent, d), latent ** -0.5)
            shapes[(layer, "moe", "shared", "up", "kernel")] = ((d, shared), d ** -0.5)
            shapes[(layer, "moe", "shared", "down", "kernel")] = ((shared, d), shared ** -0.5)
    shapes[("ln_f", "scale")] = ((d,), ("const", 1.0))
    shapes[("lm_head", "kernel")] = ((d, v), d ** -0.5)
    return shapes


def init_params(key, cfg, router_bias=None):
    """Seeded float32 weights as a nested dict, named as the program's model
    names its parameters. ``router_bias`` (``{layer: [E]}``, what
    :func:`balanced_bias` returned for the same key) takes the place of the
    seeded selection biases."""
    low, high, floor = cfg["time_step_min"], cfg["time_step_max"], cfg["time_step_floor"]
    tree = {}
    for index, (path, (shape, init)) in enumerate(leaf_shapes(cfg).items()):
        draw = jax.random.fold_in(key, index)
        if isinstance(init, tuple):
            leaf = init[1] * jnp.ones(shape, jnp.float32)
        elif init == "a_log":
            first = shares(cfg)[0] * shape[0]
            leaf = jnp.log(jnp.arange(first + 1, first + 1 + shape[0], dtype=jnp.float32))
        elif init == "dt_bias":
            step = jnp.exp(jax.random.uniform(draw, shape, jnp.float32) * (math.log(high) - math.log(low)) + math.log(low))
            step = jnp.maximum(step, floor)
            leaf = step + jnp.log(-jnp.expm1(-step))
        else:
            leaf = init * jax.random.normal(draw, shape, jnp.float32)
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaf
    for layer, bias in (router_bias or {}).items():
        tree[layer]["moe"]["router_bias"] = jnp.asarray(bias, jnp.float32)
    return tree


def parameter_count(cfg):
    return sum(int(np.prod(shape)) for shape, _ in leaf_shapes(cfg).values())


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, fake_quant(a, quant), fake_quant(b, quant))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def scan(x, delta, a, b, c, skip, ids):
    """The recurrence over the positions of every row, a plain ``lax.scan``,
    :data:`HEAD_BLOCK` heads at a time. ``x`` ``[B, L, H, P]``, ``delta``
    ``[B, L, H]``, ``a``, ``skip`` ``[H]``, ``b``, ``c`` ``[B, L, G, N]``:
    ``y`` ``[B, L, H, P]``."""
    heads, per = x.shape[2], x.shape[2] // b.shape[2]
    starts = jnp.concatenate([jnp.ones((ids.shape[0], 1), bool), ids[:, 1:] != ids[:, :-1]], axis=1)
    size = math.gcd(heads, HEAD_BLOCK)

    def row(x, delta, b, c, starts, a):
        def step(h, now):
            xx, d, bb, cc, first = now
            h = jnp.where(first, 0.0, jnp.exp(d * a)[:, None, None] * h) + (d[:, None] * xx)[..., None] * bb[:, None, :]
            return h, jnp.sum(h * cc[:, None, :], axis=-1)

        return jax.lax.scan(step, jnp.zeros(x.shape[1:] + b.shape[-1:], jnp.float32), (x, delta, b, c, starts))[1]

    @jax.checkpoint
    def block(first):
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, first, size, axis=2)  # noqa: E731
        group = (first + jnp.arange(size)) // per  # each head's group
        return jax.vmap(row, in_axes=(0, 0, 0, 0, 0, None))(
            cut(x), cut(delta), b[:, :, group], c[:, :, group], starts, jax.lax.dynamic_slice_in_dim(a, first, size))

    y = jax.lax.map(block, jnp.arange(0, heads, size))  # [blocks, B, L, size, P]
    y = jnp.moveaxis(y, 0, 2).reshape(x.shape)
    return y + skip[:, None] * x


def mamba2(u, p, ids, cfg, quant=None):
    """A Mamba-2 sub-layer over the heads and groups ``p`` holds."""
    width, states = cfg["mamba_head_dim"], cfg["ssm_state_size"]
    heads = p["a_log"].shape[0]
    inner = heads * width
    groups = (p["conv_bias"].shape[0] - inner) // (2 * states)
    batch, length = u.shape[:2]
    zxbcdt = _mm("bld,de->ble", u, p["in_proj"]["kernel"], quant)
    z, xbc, dt = zxbcdt[..., :inner], zxbcdt[..., inner:inner + p["conv_bias"].shape[0]], zxbcdt[..., -heads:]
    xbc = conv(xbc, p["conv_kernel"], p["conv_bias"], ids)
    x = xbc[..., :inner].reshape(batch, length, heads, width)
    b = xbc[..., inner:inner + groups * states].reshape(batch, length, groups, states)
    c = xbc[..., inner + groups * states:].reshape(batch, length, groups, states)
    y = scan(x, jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["a_log"]), b, c, p["skip"], ids)
    gated = (y.reshape(batch, length, inner) * jax.nn.silu(z)).reshape(batch, length, groups, inner // groups)
    gated = gated * jax.lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + cfg["norm_eps"])
    return _mm("ble,ed->bld", gated.reshape(batch, length, inner) * p["norm_scale"], p["out_proj"]["kernel"], quant)


def attention(u, p, ids, quant=None):
    """Grouped-query attention over the heads ``p`` holds, the mask written out."""
    q = _mm("bld,dhk->blhk", u, p["q"]["kernel"], quant)
    k = _mm("bld,dhk->blhk", u, p["k"]["kernel"], quant)
    v = _mm("bld,dhk->blhk", u, p["v"]["kernel"], quant)
    batch, length, heads, width = q.shape
    per = heads // k.shape[2]
    k, v = jnp.repeat(k, per, axis=2), jnp.repeat(v, per, axis=2)
    size = min(length, QUERY_BLOCK)
    place = jnp.broadcast_to(jnp.arange(length)[None, :], ids.shape)

    @jax.checkpoint
    def one_block(start):
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, start, size, axis=1)  # noqa: E731
        mask = visible(cut(ids), cut(place), ids, place, None)[:, None]
        scores = _mm("bqhk,bshk->bhqs", cut(q), k, quant) * width ** -0.5
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        probs = jnp.where(mask, probs, 0.0)  # a row that sees nothing (padding) attends to nothing
        return _mm("bhqs,bshk->bqhk", probs, v, quant)

    out = jax.lax.map(one_block, jnp.arange(0, length, size))  # [blocks, B, size, H, w]
    out = jnp.moveaxis(out, 0, 1).reshape(batch, length, heads, width)
    return _mm("blhk,hkd->bld", out, p["o"]["kernel"], quant)


def relu2(x, up, down, quant):
    return _mm("tf,fd->td", jnp.square(jax.nn.relu(_mm("td,df->tf", x, up, quant))), down, quant)


def experts(u, p, cfg, quant=None, held=None, shared=True):
    """A latent mixture-of-experts sub-layer on ``u`` ``[B, L, d]``: the held
    experts' weighted outputs in the latent (``held``: first, count; default
    the configuration's), projected up, plus, with ``shared``, the shared
    expert's."""
    first, count = held if held is not None else cfg["experts_held"]
    flat = u.reshape(-1, u.shape[-1])
    weights = routing(flat, p, cfg, quant)
    low = _mm("td,dl->tl", flat, p["latent_down"]["kernel"], quant)
    out = jnp.zeros_like(low)
    for e in range(count):
        out = out + weights[:, first + e, None] * relu2(low, p["experts_up"][e], p["experts_down"][e], quant)
    out = _mm("tl,ld->td", out, p["latent_up"]["kernel"], quant)
    if shared:
        out = out + relu2(flat, p["shared"]["up"]["kernel"], p["shared"]["down"]["kernel"], quant)
    return out.reshape(u.shape)


def block_forward(x, p, ids, cfg, index, quant=None, routed=experts):
    kind, eps = block_kind(cfg, index), cfg["norm_eps"]
    if kind == "mamba2":
        return x + mamba2(_rms(x, p["ln1"]["scale"], eps), p["mamba2"], ids, cfg, quant)
    if kind == "attn":
        return x + attention(_rms(x, p["ln1"]["scale"], eps), p["attn"], ids, quant)
    return x + routed(_rms(x, p["ln2"]["scale"], eps), p["moe"], cfg, quant)


def logits_of(params, tokens, ids, cfg, quant=None):
    x = params["embed"]["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda x, p, i=i: block_forward(x, p, ids, cfg, i, quant))(x, params["layer_{}".format(i)])
    x = _rms(x, params["ln_f"]["scale"], cfg["norm_eps"])
    return _mm("bld,dv->blv", x, params["lm_head"]["kernel"], quant)


def balanced_bias(key, cfg, batch):
    """``{layer: b}`` for the weights of ``key``: every routed block's seeded
    selection bias moved by ``noaux_tc``'s own rule
    (``reference/moe_lm.balance``'s, here over the real positions alone: a
    padding position, segment id 0, takes no slot in the program, the
    configuration's ``padding_slots``) until every expert of the model is
    chosen about equally often on ``batch``, block by block in one forward
    pass, each block routing with its balanced bias before the next is looked
    at."""
    params = init_params(key, cfg)
    tokens, ids = batch["tokens"][:, :-1], batch["segment_ids"][:, :-1]
    real = (ids.reshape(-1) > 0).astype(jnp.float32)
    k, (first, last) = cfg["num_experts_per_tok"], BALANCE_STEPS
    found = {}

    def balance(scores, bias):
        def body(i, b):
            _, chosen = jax.lax.top_k(scores + b, k)
            load = jnp.einsum("tke,t->e", jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32), real)
            return b + first * (last / first) ** (i / (BALANCE_ROUNDS - 1.0)) * jnp.sign(jnp.mean(load) - load)

        return jax.lax.fori_loop(0, BALANCE_ROUNDS, body, bias)

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens]
        for i in range(cfg["num_hidden_layers"]):
            layer = "layer_{}".format(i)

            def routed(u, p, cfg, quant, layer=layer):
                found[layer] = balance(router_scores(u.reshape(-1, u.shape[-1]), p, quant), p["router_bias"])
                return experts(u, dict(p, router_bias=found[layer]), cfg, quant)

            x = block_forward(x, params[layer], ids, cfg, i, routed=routed)
    return found


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


def share_params(p, cfg, kind, index, of):
    """One share's cut of a whole block's parameters ``p`` (``kind``
    ``mamba2``: heads and whole groups; ``attn``: query heads with the
    key/value heads they read; ``moe``: routed experts), share ``index`` of
    ``of``. What every share holds alike (norms, router, latent projections,
    shared expert) is handed on as it is."""
    def part(t, axis=0):
        size = t.shape[axis] // of
        return jax.lax.slice_in_dim(t, index * size, (index + 1) * size, axis=axis)

    def columns(t, axis, widths):
        """Each of the side-by-side pieces of ``t`` cut for itself."""
        pieces = jnp.split(t, np.cumsum(widths)[:-1], axis=axis)
        return jnp.concatenate([part(piece, axis) for piece in pieces], axis=axis)

    if kind == "moe":
        return dict(p, experts_up=part(p["experts_up"]), experts_down=part(p["experts_down"]))
    if kind == "attn":
        def kv_part(t):  # its share of the key/value heads, or the one head its query heads read
            return part(t, 1) if t.shape[1] >= of else t[:, index * t.shape[1] // of:][:, :1]

        return {"q": {"kernel": part(p["q"]["kernel"], 1)}, "k": {"kernel": kv_part(p["k"]["kernel"])},
                "v": {"kernel": kv_part(p["v"]["kernel"])}, "o": {"kernel": part(p["o"]["kernel"])}}
    heads, states = p["a_log"].shape[0], cfg["ssm_state_size"]
    inner = heads * cfg["mamba_head_dim"]
    wide = (p["conv_bias"].shape[0] - inner) // 2  # a group's states, all groups
    assert wide % (of * states) == 0, "a share holds whole groups"
    widths = [inner, inner, wide, wide, heads]  # z | x | B | C | dt
    return {
        "in_proj": {"kernel": columns(p["in_proj"]["kernel"], 1, widths)},
        "conv_kernel": columns(p["conv_kernel"], 1, widths[1:4]), "conv_bias": columns(p["conv_bias"], 0, widths[1:4]),
        "dt_bias": part(p["dt_bias"]), "a_log": part(p["a_log"]), "skip": part(p["skip"]),
        "norm_scale": part(p["norm_scale"]), "out_proj": {"kernel": part(p["out_proj"]["kernel"])},
    }


def follow(cfg, key, batches, devices, quant=None, router_bias=None):
    """Train from the seeded weights (``init_params(key, cfg, router_bias)``),
    all but the parameters the configuration's ``optimizer.frozen`` names,
    over ``batches`` (host dicts of int32 ``[rows, seq+1]`` arrays). Returns
    each step's loss, the first step's gradient norm and sketch per leaf and
    the norm per leaf of the parameters' change over all the steps, without
    the quiet leaves (``reference/moe_lm.follow``'s rule: a leaf whose
    gradient's root mean square stayed under AdamW's ``eps`` in every step —
    here the selection biases, which no gradient reaches; named in one printed
    line). Rows go through the gradient function one at a time on the first
    device; AdamW's moments stay on the host and come to the device a leaf at
    a time."""
    device = devices[0]
    init = jax.jit(lambda k, bias: init_params(k, cfg, bias))
    grad_fn = jax.jit(make_grad_fn(cfg, quant))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
    update = jax.jit(lambda p, g, m, v, count: adamw_leaf(p, g, m, v, count, cfg["optimizer"]), donate_argnums=(0, 1))
    norms, sketches = jax.jit(leaf_norms), jax.jit(leaf_sketches)

    with jax.default_device(device):
        params = init(key, router_bias)
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
        change = {k: float(v) for k, v in jax.device_get(norms(params, init(key, router_bias))).items()}
    quiet = {name: rms for name, rms in loudest.items() if rms < cfg["optimizer"]["eps"]}
    print("reference{}: left out, gradient rms under {:g}: {}".format(
        " (" + quant + ")" if quant else "", cfg["optimizer"]["eps"],
        ", ".join("{} {:.3g}".format(name, rms) for name, rms in sorted(quiet.items())) or "none"), flush=True)

    def heard(readings):
        return {name: value for name, value in readings.items() if name not in quiet}

    return {"losses": losses, "first_grad": heard(first_grad), "first_grad_sketch": heard(first_sketch),
            "param_change": heard(change)}
