"""Plain float32 reference for the ``moe_lm`` family: a decoder of latent
attention, routed + shared SwiGLU experts (a chip's share of them) and
manifold-constrained hyper-connections — forward pass, loss, gradients and
AdamW step in straightforward ``jax.numpy``, nothing imported from the
program.

The layer follows ``configs/xing4-a4b.json`` (T tokens, d hidden, n =
``hc_mult`` streams, RMSNorm eps ``rms_norm_eps``, no biases):

* **Latent attention.** ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb``, per
  head ``[q_nope, q_rope]``. ``[c_kv, k_rope] = x W_kva``; ``c_kv =
  RMSNorm(c_kv)``; per head ``[k_nope, v] = c_kv W_kvb``; ``k_rope`` is one
  vector for all heads. Rotary positions (restarting in every packed
  document) on ``q_rope`` and ``k_rope`` with YaRN frequencies. Scores
  ``(q_nope . k_nope + q_rope . k_rope) * (nope + rope)^-1/2 * m^2``, ``m =
  0.1 ln(factor) + 1``, causal and fenced to the segment, written out (a
  block of queries at a time, so that a row of 8192 fits); softmax; times
  ``v``; ``W_o``.
* **Experts.** ``s = sigmoid(x W_r)`` over all the model's experts; chosen:
  top-k of ``s + b``; weights: ``s`` at the chosen over their sum (+ 1e-20)
  times ``routed_scaling_factor``. Every expert held here is applied to
  *every* token and the result masked by the routing — no sort, no gather —
  then the shared expert is added. Experts held elsewhere add nothing.
* **Hyper-connections** around each sub-layer ``F``: ``x~ = vec(X) /
  rms(vec(X))``; ``H_pre = sigmoid(a_pre x~ phi_pre + b_pre)``, ``H_post = 2
  sigmoid(a_post x~ phi_post + b_post)``, ``H_res = SK(clamp(a_res mat(x~
  phi_res) + b_res))`` with ``SK`` = exp, then ``hc_sinkhorn_iters`` times
  rows to sum 1, columns to sum 1 (denominators + ``hc_eps``), as a loop;
  ``h = sum_i H_pre,i X_i``; ``X'_i = sum_j H_res,ij X_j + H_post,i F(h)``.
  The embedding is copied to the n streams; after the last layer the
  streams are summed.

Departures from the published description (each also under ``assumed`` in
the configuration): rotary pairs interleaved (the family's convention; the
config has no ``rope_interleave``); the selection bias ``b`` seeded, moved to
balance by its own rule on the cell's first batch (:func:`balanced_bias`:
what pre-training would have left) and from then on touched by the
optimizer's weight decay alone (no gradient reaches it); no auxiliary loss
(``noaux_tc``);
where ``hc_eps`` and the clamp apply, and the sum at the end, as above — the
config names the sizes, not the places.

``init_params`` is the benchmark's seeded weights; ``families/moe_lm.py``
hands the same function to the program. The optimizer is AdamW on every
parameter but those the configuration's ``optimizer.frozen`` names (the
routers' matrices), which stay where they are. ``quant="fp8"`` is the control:
every matrix product takes its operands rounded to float8.

Memory: at the cell's size the parameters, their gradient and AdamW's two
moments are 12.1 GB. The moments live on the host between updates and the
update goes leaf by leaf, so the device holds parameters, gradients and one
row's activations.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import sketch
from benchmarks.reference.control import fake_quant

QUERY_BLOCK = 512

def leaf_shapes(cfg):
    """``{path: (shape, init)}``: ``init`` a normal's std, or ``("const",
    value)``, or ``("diag", value)`` for a scaled identity."""
    d, v, n = cfg["hidden_size"], cfg["vocab_size"], cfg["hc_mult"]
    heads, nope, rope, v_dim = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                                cfg["v_head_dim"])
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    held, width = cfg["experts_held"][1], cfg["moe_intermediate_size"]
    shapes = {("embed", "embedding"): ((v, d), 1.0)}
    for i in range(cfg["num_hidden_layers"]):
        layer = "layer_{}".format(i)
        for res in ("res_attn", "res_mlp"):
            for name, width_out in (("phi_pre", n), ("phi_post", n), ("phi_res", n * n)):
                shapes[(layer, res, name)] = ((n, d, width_out), (n * d) ** -0.5)
            for name in ("alpha_pre", "alpha_post", "alpha_res"):
                shapes[(layer, res, name)] = ((), ("const", 0.01))
            shapes[(layer, res, "b_pre")] = ((n,), 0.5)
            shapes[(layer, res, "b_post")] = ((n,), 0.5)
            shapes[(layer, res, "b_res")] = ((n, n), ("diag", 2.0))
        shapes[(layer, "ln1", "scale")] = ((d,), ("const", 1.0))
        shapes[(layer, "attn", "q_a", "kernel")] = ((d, q_rank), d ** -0.5)
        shapes[(layer, "attn", "q_norm", "scale")] = ((q_rank,), ("const", 1.0))
        shapes[(layer, "attn", "q_b", "kernel")] = ((q_rank, heads, nope + rope), q_rank ** -0.5)
        shapes[(layer, "attn", "kv_a", "kernel")] = ((d, kv_rank + rope), d ** -0.5)
        shapes[(layer, "attn", "kv_norm", "scale")] = ((kv_rank,), ("const", 1.0))
        shapes[(layer, "attn", "kv_b", "kernel")] = ((kv_rank, heads, nope + v_dim), kv_rank ** -0.5)
        shapes[(layer, "attn", "o", "kernel")] = ((heads, v_dim, d), (heads * v_dim) ** -0.5)
        shapes[(layer, "ln2", "scale")] = ((d,), ("const", 1.0))
        if i < cfg["first_k_dense_replace"]:
            ff = cfg["intermediate_size"]
            shapes[(layer, "mlp", "gate", "kernel")] = ((d, ff), d ** -0.5)
            shapes[(layer, "mlp", "up", "kernel")] = ((d, ff), d ** -0.5)
            shapes[(layer, "mlp", "down", "kernel")] = ((ff, d), ff ** -0.5)
        else:
            shared = width * cfg["n_shared_experts"]
            shapes[(layer, "moe", "router")] = ((d, cfg["router_experts"]), d ** -0.5)
            shapes[(layer, "moe", "router_bias")] = ((cfg["router_experts"],), 0.02)
            shapes[(layer, "moe", "experts_gate")] = ((held, d, width), d ** -0.5)
            shapes[(layer, "moe", "experts_up")] = ((held, d, width), d ** -0.5)
            shapes[(layer, "moe", "experts_down")] = ((held, width, d), width ** -0.5)
            shapes[(layer, "moe", "shared", "gate", "kernel")] = ((d, shared), d ** -0.5)
            shapes[(layer, "moe", "shared", "up", "kernel")] = ((d, shared), d ** -0.5)
            shapes[(layer, "moe", "shared", "down", "kernel")] = ((shared, d), shared ** -0.5)
    shapes[("ln_f", "scale")] = ((d,), ("const", 1.0))
    shapes[("lm_head", "kernel")] = ((d, v), d ** -0.5)
    return shapes


def init_params(key, cfg, router_bias=None):
    """Seeded float32 weights as a nested dict, named as the program's model
    names its parameters, so the same tree serves both sides. ``key`` is the
    run's (``--seed``). ``router_bias`` (``{layer: [E]}``, what
    :func:`balanced_bias` returned for the same key) takes the place of the
    seeded selection biases."""
    tree = {}
    for index, (path, (shape, init)) in enumerate(leaf_shapes(cfg).items()):
        if isinstance(init, tuple):
            kind, value = init
            leaf = value * (jnp.eye(shape[0], dtype=jnp.float32) if kind == "diag" else jnp.ones(shape, jnp.float32))
        else:
            leaf = init * jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)
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


def yarn_inv_freq(cfg):
    """The rotary part's inverse frequencies under YaRN, in closed form: with
    ``theta_i = theta^(2i/dim)``, ``1/theta_i`` below the correction range,
    ``1/(factor theta_i)`` above it, and a linear blend inside."""
    dim, theta, s = cfg["qk_rope_head_dim"], float(cfg["rope_theta"]), cfg["rope_scaling"]

    def dim_of(rotations):
        return dim * math.log(s["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(s["beta_fast"])), 0)
    high = min(math.ceil(dim_of(s["beta_slow"])), dim - 1)
    out = []
    for i in range(dim // 2):
        kept = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
        out.append(kept / s["factor"] * ramp + kept * (1.0 - ramp))
    return jnp.asarray(out, jnp.float32)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope(x, positions, cfg):
    """Interleaved pairs: (x0, x1), (x2, x3), … each rotated by its
    frequency times the position. ``x`` is ``[B, L, …, D]``."""
    s = cfg["rope_scaling"]
    scale = _mscale(s["factor"], s["mscale"]) / _mscale(s["factor"], s["mscale_all_dim"])
    angles = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(cfg)
    while angles.ndim < x.ndim:
        angles = angles[:, :, None]
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return turned.reshape(x.shape)


def attention(x, p, positions, seg, cfg, quant=None):
    nope, rank, eps = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    c_q = _rms(_mm("bld,dr->blr", x, p["q_a"]["kernel"], quant), p["q_norm"]["scale"], eps)
    q = _mm("blr,rhk->blhk", c_q, p["q_b"]["kernel"], quant)
    kv = _mm("bld,dr->blr", x, p["kv_a"]["kernel"], quant)
    c_kv = _rms(kv[..., :rank], p["kv_norm"]["scale"], eps)
    up = _mm("blr,rhk->blhk", c_kv, p["kv_b"]["kernel"], quant)
    k_nope, v = up[..., :nope], up[..., nope:]
    q_rope = _rope(q[..., nope:], positions, cfg)
    k_rope = _rope(kv[..., rank:], positions, cfg)  # [B, L, rope]: shared by the heads
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], k_nope.shape[:-1] + k_rope.shape[-1:])], -1)
    scale = q.shape[-1] ** -0.5 * _mscale(cfg["rope_scaling"]["factor"], cfg["rope_scaling"]["mscale_all_dim"]) ** 2

    length = x.shape[1]
    block = min(length, QUERY_BLOCK)
    key_at = jnp.arange(length)

    @jax.checkpoint
    def one_block(start):
        q_block = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        seg_block = jax.lax.dynamic_slice_in_dim(seg, start, block, axis=1)
        scores = _mm("bqhk,bshk->bhqs", q_block, k, quant) * scale
        mask = ((start + jnp.arange(block))[:, None] >= key_at[None, :])[None, None]
        mask = mask & (seg_block[:, None, :, None] == seg[:, None, None, :])
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return _mm("bhqs,bshk->bqhk", probs, v, quant)

    out = jax.lax.map(one_block, jnp.arange(0, length, block))  # [blocks, B, block, H, v]
    out = jnp.moveaxis(out, 0, 1).reshape(x.shape[0], length, out.shape[-2], out.shape[-1])
    return _mm("blhk,hkd->bld", out, p["o"]["kernel"], quant)


def swiglu(x, gate, up, down, quant):
    hidden = jax.nn.silu(_mm("td,df->tf", x, gate, quant)) * _mm("td,df->tf", x, up, quant)
    return _mm("tf,fd->td", hidden, down, quant)


def router_scores(x, p, quant=None):
    return jax.nn.sigmoid(_mm("td,de->te", x, p["router"], quant))


def routing(x, p, cfg, quant=None):
    """``[T, E]`` weight of every expert of the model for every token: 0
    where the expert was not chosen."""
    scores = router_scores(x, p, quant)
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(p["router_bias"]), cfg["num_experts_per_tok"])
    is_chosen = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=scores.dtype), axis=1)
    picked = scores * is_chosen
    return picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]


def experts(x, p, cfg, quant=None, held=None, shared=True):
    """The feed-forward of a routed layer on ``x`` ``[B, L, d]``: the held
    experts' weighted outputs (``held``: first, count; default the
    configuration's) plus, with ``shared``, the shared expert."""
    first, count = held if held is not None else cfg["experts_held"]
    flat = x.reshape(-1, x.shape[-1])
    weights = routing(flat, p, cfg, quant)
    out = jnp.zeros_like(flat)
    for e in range(count):
        y = swiglu(flat, p["experts_gate"][e], p["experts_up"][e], p["experts_down"][e], quant)
        out = out + weights[:, first + e, None] * y
    if shared:
        s = p["shared"]
        out = out + swiglu(flat, s["gate"]["kernel"], s["up"]["kernel"], s["down"]["kernel"], quant)
    return out.reshape(x.shape)


def sinkhorn(logits, cfg):
    def body(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + cfg["hc_eps"])
        return m / (jnp.sum(m, axis=-2, keepdims=True) + cfg["hc_eps"])

    return jax.lax.fori_loop(0, cfg["hc_sinkhorn_iters"], body, jnp.exp(logits))


def hyper_maps(streams, p, cfg, quant=None):
    """``(H_pre [.., n], H_post [.., n], H_res [.., n, n])`` of ``streams``
    ``[B, L, n, d]``."""
    n = streams.shape[-2]
    vec = streams.reshape(streams.shape[:-2] + (-1,))
    vec = vec / jnp.sqrt(jnp.mean(jnp.square(vec), axis=-1, keepdims=True))

    def project(phi):
        return _mm("blv,vk->blk", vec, phi.reshape(-1, phi.shape[-1]), quant)

    h_pre = jax.nn.sigmoid(p["alpha_pre"] * project(p["phi_pre"]) + p["b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(p["alpha_post"] * project(p["phi_post"]) + p["b_post"])
    raw = p["alpha_res"] * project(p["phi_res"]).reshape(vec.shape[:-1] + (n, n)) + p["b_res"]
    h_res = sinkhorn(jnp.clip(raw, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]), cfg)
    return h_pre, h_post, h_res


def hyper_connected(streams, p, sublayer, cfg, quant=None):
    h_pre, h_post, h_res = hyper_maps(streams, p, cfg, quant)
    y = sublayer(jnp.einsum("bln,blnd->bld", h_pre, streams))
    return jnp.einsum("blij,bljd->blid", h_res, streams) + h_post[..., None] * y[:, :, None, :]


def layer_forward(streams, p, positions, seg, cfg, quant=None, routed=experts):
    eps = cfg["rms_norm_eps"]
    streams = hyper_connected(
        streams, p["res_attn"],
        lambda h: attention(_rms(h, p["ln1"]["scale"], eps), p["attn"], positions, seg, cfg, quant), cfg, quant)

    def feed_forward(h):
        h = _rms(h, p["ln2"]["scale"], eps)
        if "moe" in p:
            return routed(h, p["moe"], cfg, quant)
        m = p["mlp"]
        flat = swiglu(h.reshape(-1, h.shape[-1]), m["gate"]["kernel"], m["up"]["kernel"], m["down"]["kernel"], quant)
        return flat.reshape(h.shape)

    return hyper_connected(streams, p["res_mlp"], feed_forward, cfg, quant)


def logits_of(params, tokens, positions, seg, cfg, quant=None):
    x = params["embed"]["embedding"][tokens]
    streams = jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (cfg["hc_mult"], x.shape[-1]))
    for i in range(cfg["num_hidden_layers"]):
        streams = jax.checkpoint(
            lambda s, p: layer_forward(s, p, positions, seg, cfg, quant))(streams, params["layer_{}".format(i)])
    x = _rms(jnp.sum(streams, axis=-2), params["ln_f"]["scale"], cfg["rms_norm_eps"])
    return _mm("bld,dv->blv", x, params["lm_head"]["kernel"], quant)


#: rounds of the balancing rule, and its step from the first round to the last
BALANCE_ROUNDS, BALANCE_STEPS = 400, (0.03, 1e-5)


def balance(scores, bias, k):
    """The selection bias moved by ``noaux_tc``'s own rule until every expert
    is chosen about equally often on ``scores`` ``[T, E]``: each round an
    expert chosen more often than the mean loses a step of bias and one chosen
    less often gains it. The step falls geometrically (the published rule
    keeps one small step over many thousands of batches)."""
    first, last = BALANCE_STEPS

    def body(i, b):
        _, chosen = jax.lax.top_k(scores + b, k)
        load = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32), axis=(0, 1))
        step = first * (last / first) ** (i / (BALANCE_ROUNDS - 1.0))
        return b + step * jnp.sign(jnp.mean(load) - load)

    return jax.lax.fori_loop(0, BALANCE_ROUNDS, body, bias)


def balanced_bias(key, cfg, batch):
    """``{layer: b}`` for the weights of ``key``: every routed layer's seeded
    selection bias, balanced on ``batch`` (:func:`balance`) layer by layer in
    one forward pass, each layer routing with its balanced bias before the
    next is looked at.

    A published model comes with its biases balanced: the rule ran all through
    pre-training. Seeded weights do not. Every token's hidden state shares a
    large common part and a Zipf law's few very frequent words go where their
    embedding sends them, so under a seeded bias a handful of experts take
    most of the slots: the experts held here got 9% to 21% of them from seed
    to seed and up to 5 times the mean on the fullest, and the rate followed
    the held share (-0.14% a point; my chip runs, PR 26)."""
    params = init_params(key, cfg)
    tokens, seg, pos = batch["tokens"][:, :-1], batch["segment_ids"][:, :-1], batch["positions"][:, :-1]
    found = {}
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens]
        streams = jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (cfg["hc_mult"], x.shape[-1]))
        for i in range(cfg["num_hidden_layers"]):
            layer = "layer_{}".format(i)

            def routed(h, p, cfg, quant, layer=layer):
                scores = router_scores(h.reshape(-1, h.shape[-1]), p, quant)
                found[layer] = balance(scores, p["router_bias"], cfg["num_experts_per_tok"])
                return experts(h, dict(p, router_bias=found[layer]), cfg, quant)

            streams = layer_forward(streams, params[layer], pos, seg, cfg, routed=routed)
    return found


def loss_sum(params, batch, cfg, quant=None):
    """Sum (not mean) of the valid targets' cross-entropy over the rows of
    ``batch``: rows can be processed one at a time and added."""
    tokens, seg, pos = batch["tokens"], batch["segment_ids"], batch["positions"]
    logits = logits_of(params, tokens[:, :-1], pos[:, :-1], seg[:, :-1], cfg, quant)
    targets = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    valid = ((seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] > 0)).astype(jnp.float32)
    return jnp.sum((logz - picked) * valid)


def valid_targets(batch):
    seg = np.asarray(batch["segment_ids"])
    return float(((seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] > 0)).sum())


def make_grad_fn(cfg, quant=None):
    """``grad_fn(params, rows, scale) -> (loss, grads)``: ``scale`` is one
    over the whole batch's valid-target count, so rows add up to the batch's
    mean loss and its gradient."""

    def block_loss(params, rows, scale):
        with jax.default_matmul_precision("highest"):
            return loss_sum(params, rows, cfg, quant) * scale

    return jax.value_and_grad(block_loss)


def adamw_leaf(p, g, m, v, count, opt):
    """One AdamW step of one leaf as optax.adamw composes it: bias-corrected
    moments, decoupled weight decay added to the update, then the rate."""
    b1, b2 = opt["b1"], opt["b2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    update = (m / (1 - b1 ** count)) / (jnp.sqrt(v / (1 - b2 ** count)) + opt["eps"])
    return p - opt["learning_rate"] * (update + opt["weight_decay"] * p), m, v


def _names(tree):
    return ["/".join(k.key for k in path) for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree, other=None):
    if other is not None:
        tree = jax.tree.map(jnp.subtract, tree, other)
    return {name: jnp.sqrt(jnp.sum(jnp.square(leaf))) for name, leaf in zip(_names(tree), jax.tree.leaves(tree))}


def leaf_sketches(tree, key):
    return {name: sketch.leaf_sketch(leaf, key, name) for name, leaf in zip(_names(tree), jax.tree.leaves(tree))}


def follow(cfg, key, batches, devices, quant=None, router_bias=None):
    """Train from the seeded weights (``init_params(key, cfg, router_bias)``),
    all but the parameters the configuration's ``optimizer.frozen`` names,
    over ``batches`` (host dicts of int32 ``[rows, seq+1]`` arrays). Returns
    each step's loss, the first step's gradient norm and sketch per leaf and
    the norm per leaf of the parameters' change over all the steps, without
    the quiet leaves (named in one printed line). Rows go through the gradient function one at a time on the
    first device; AdamW's moments stay on the host and come to the device a
    leaf at a time."""
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
    # A leaf is quiet, and left out of the comparison, when its gradient's root
    # mean square stayed under AdamW's ``eps`` in every step: this side's
    # optimizer does not see it. AdamW scales whatever it sees to a full-size
    # step, so where the exact gradient is zero the float32 reference stays put
    # (its rounding is under ``eps``) and a bfloat16 program, whose rounding is
    # not, moves by the rate every step, and neither is wrong. Here: the first
    # sub-layer's ``H_pre`` and ``H_res`` maps (they see the embedding copied
    # to all streams: ``H_pre`` only scales ``h``, which the pre-norm removes,
    # and ``H_res``, rows summing to 1, mixes equal streams into themselves),
    # the last sub-layer's ``H_res`` (columns summing to 1, the streams summed
    # next) and the selection bias, which no gradient reaches (``tests/`` hold
    # the program to an exactly zero gradient there).
    quiet = {name: rms for name, rms in loudest.items() if rms < cfg["optimizer"]["eps"]}
    print("reference{}: left out, gradient rms under {:g}: {}".format(
        " (" + quant + ")" if quant else "", cfg["optimizer"]["eps"],
        ", ".join("{} {:.3g}".format(name, rms) for name, rms in sorted(quiet.items())) or "none"), flush=True)

    def heard(readings):
        return {name: value for name, value in readings.items() if name not in quiet}

    return {"losses": losses, "first_grad": heard(first_grad), "first_grad_sketch": heard(first_sketch),
            "param_change": heard(change)}
