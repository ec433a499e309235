"""Decoder-only transformer LM — the long-context flagship model.

No counterpart exists in the reference (its models are CNNs; SURVEY.md §5
notes sequence parallelism is entirely absent) — this model is the showcase
for the capabilities the TPU build adds: bfloat16 compute on the MXU, rotary
positions, and attention that transparently switches to **ring attention**
over the ``sp`` mesh axis for sequences too long for one chip
(:mod:`tensorflowonspark_tpu.parallel.ring_attention`).

Sharding: ``param_specs`` gives each weight a PartitionSpec combining tensor
parallelism (``tp``: attention heads / MLP hidden sharded) with FSDP
(``fsdp``: remaining large dims), and the model inserts activation sharding
constraints so XLA keeps activations distributed across dp/sp/tp instead of
gathering them.

``remat=True`` recomputes each block in the backward pass (``nn.remat``) from
what the block keeps (:data:`~tensorflowonspark_tpu.ops.flash_attention.REMAT_POLICY`),
in bfloat16 a token and layer: its input (2 · d_model bytes), the results of
the q, k and v projections (3 · 2 · d_model), the attention sub-layer's result
after the output projection (2 · d_model) and, on the flash path, what the
attention kernel hands its backward: its output with its heads merged (another
2 · d_model) and one float32 a position and head. 12 · d_model + 4 · n_heads
bytes a token and layer in all, 201 MB a layer at d_model 1024 and 4 rows of
4096. The recomputed pass therefore runs neither the forward kernel nor any of
the attention's four products (on the ``plain`` and ``ring`` paths: none of the
four products; the attention itself again): it begins, in effect, at the
mid-block residual, with the second norm and the MLP's first product. A job
that fitted its chip by less than that fails at compile time with XLA's
out-of-memory message; what it saves is the recomputation of a third of a
block's products at d_ff = 4 · d_model.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from tensorflowonspark_tpu.models import register
from tensorflowonspark_tpu.ops import flash_blocks
from tensorflowonspark_tpu.ops.flash_attention import KEPT_ATTENDED, KEPT_PROJECTED, REMAT_POLICY, flash_attention
from tensorflowonspark_tpu.parallel.ring_attention import (
    plain_attention,
    ring_attention_sharded,
)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 6
    n_heads: int = 8
    d_ff: int = 2048
    max_seq_len: int = 2048
    dtype: str = "float32"  # compute dtype; params stay float32
    #: recompute each block in the backward pass, FLOPs for HBM: a block keeps
    #: its input, the attention sub-layer's products (q, k, v and the
    #: sub-layer's result) and, on the flash path, the attention's output and
    #: one float32 a position and head (the module's text has the bytes)
    remat: bool = False
    #: "auto" — ring over sp when the mesh has it, else the pallas flash
    #: kernel on TPU, else plain XLA attention; or force "flash" (TPU only),
    #: "flash_interpret" (the kernel in the Pallas interpreter), "plain", "ring"
    attention: str = "auto"

    @property
    def head_dim(self):
        return self.d_model // self.n_heads

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


def _rope(x, positions, base=10000.0):
    """Rotary position embedding over the last (head) dim, its two halves
    rotated together (``rotate_half``); x: [B, L, H, D]."""
    d = x.shape[-1]
    half = d // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[:, :, None].astype(jnp.float32) * freqs  # [B, L, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


_ATTENTION_IMPLS = ("auto", "flash", "flash_interpret", "plain", "ring")

#: below this sequence length ``auto`` dispatch uses plain XLA attention on
#: TPU instead of pad-to-128 + flash: the floor only guards the
#: tiny-sequence regime where the padding is most of the work (the init
#: probe batch, unit-test shapes). Not re-measured on the bare chip.
_FLASH_MIN_SEQ = int(os.environ.get("TOS_FLASH_MIN_SEQ", "256"))


def _batch_axes(mesh, batch):
    """The data axes (``dp``, ``fsdp``) of ``mesh`` that ``batch`` rows shard
    over, as a PartitionSpec entry. An axis whose size does not divide is
    dropped (degrade-to-replicated, same contract as :func:`param_specs`) —
    ``module.init`` probes run a batch of one."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes, div = [], 1
    for a in ("dp", "fsdp"):
        if a in sizes and batch % (div * sizes[a]) == 0:
            axes.append(a)
            div *= sizes[a]
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else tuple(axes)


def _flash(q, k, v, segment_ids, mesh, interpret, scale=None, rule="causal", labels=None, window=None):
    """The pallas flash kernel on ``[B, H, L, D]`` (``k`` and ``v`` may have
    fewer heads: key/value groups), padded to the kernel's 128-row granule
    and run per shard. ``scale`` is the softmax scale where it is not ``D **
    -0.5``; ``rule`` with ``labels`` (block diffusion) or ``window`` are the
    kernel's.

    A Mosaic custom call has no partitioning rule, so under pjit XLA would
    gather q/k/v and run the whole global batch's attention on every chip.
    On a multi-device mesh the call therefore goes through ``shard_map``:
    batch over the data axes, heads over ``tp`` — attention is independent
    across both, so no collective is needed inside."""
    seq = q.shape[2]
    pad = (-seq) % flash_blocks.GRANULE
    if pad:
        # causal masking means queries < seq never attend to the zero
        # padding appended after them, so pad-run-slice is exact; with
        # segments the appended columns get id 0, which never equals a
        # real (>= 1) segment — exact for the same reason
        q, k, v = (jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0))) for t in (q, k, v))
        if segment_ids is not None:
            segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad)))
        if labels is not None:  # beside id 0, which the rule shows nothing
            labels = jnp.pad(labels, ((0, 0), (0, pad)))

    def local(q, k, v, seg=None, labels=None):
        # positions play no part in the second rule: its mask is the labels'
        return flash_attention(
            q, k, v, causal=rule != "block_diffusion", scale=scale, segment_ids=seg, interpret=interpret, rule=rule,
            labels=labels, window=window)

    if mesh is None or mesh.size == 1:
        out = local(q, k, v, segment_ids, labels)
    else:
        from jax.sharding import PartitionSpec as P

        from tensorflowonspark_tpu.parallel.collectives import shard_map

        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        batch = _batch_axes(mesh, q.shape[0])
        # key/value heads fewer than the tp axis stay whole, and the query heads with them
        heads = "tp" if "tp" in sizes and q.shape[1] % sizes["tp"] == 0 and k.shape[1] % sizes["tp"] == 0 else None
        spec = P(batch, heads, None, None)
        operands, in_specs = (q, k, v), (spec, spec, spec)
        if segment_ids is not None:
            operands, in_specs = operands + (segment_ids,), in_specs + (P(batch, None),)
        if labels is not None:
            operands, in_specs = operands + (labels,), in_specs + (P(batch, None),)
        # check_vma off: pallas_call outputs carry no varying-axes type
        out = shard_map(
            local, mesh=mesh, in_specs=in_specs, out_specs=spec, check_vma=False
        )(*operands)
    return out[:, :, :seq] if pad else out


def _masked_attention(q, k, v, mask, scale=None):
    """Attention under a mask written out, ``bool [B, L, L]`` (query, key),
    float32 inside; ``k`` and ``v`` may have fewer heads than ``q``. For the
    rows the ``plain`` path is for: the mask is the row's length squared."""
    batch, heads, length, width = q.shape
    group = heads // k.shape[1]
    grouped = q.astype(jnp.float32).reshape(batch, k.shape[1], group, length, width)
    scores = jnp.einsum("bngqd,bnkd->bngqk", grouped, k.astype(jnp.float32)) * (width ** -0.5 if scale is None else scale)
    probs = jax.nn.softmax(jnp.where(mask[:, None, None], scores, -0.7 * jnp.finfo(jnp.float32).max), axis=-1)
    out = jnp.einsum("bngqk,bnkd->bngqd", probs, v.astype(jnp.float32))
    return out.reshape(batch, heads, length, v.shape[-1]).astype(q.dtype)


def _dispatch_attention(q, k, v, impl, mesh, segment_ids=None, scale=None, rule="causal", labels=None, window=None):
    """Pick the attention path. ``auto``: ring over ``sp`` when the mesh
    shards the sequence, else the pallas flash kernel on TPU (plain below
    ``TOS_FLASH_MIN_SEQ``), else plain XLA attention. Forcing
    ``plain``/``flash``/``ring`` always wins (``plain`` on an sp mesh is the
    debugging escape hatch — correct, just unsharded math).

    No path is taken quietly in place of the one asked for: ``flash`` off
    TPU is an error, not an interpreted kernel — the Pallas interpreter is
    what ``flash_interpret`` names, for CPU tests of the kernel's math.

    ``segment_ids`` (``int32 [B, L]``, 0 = padding) is the text plane's
    packed-sequence fence — every path turns it into the same
    block-diagonal mask, so packed neighbours never cross-attend. ``scale``
    is the softmax scale where it is not the key width's inverse root; ``v``
    may be narrower than ``q`` and ``k`` (latent attention), and ``k`` and
    ``v`` may have fewer heads than ``q`` (key/value groups: the flash
    kernels read a group's head in place, the other paths repeat it).

    ``rule="block_diffusion"`` with ``labels`` (``int32 [B, L]``) is the
    second mask (:mod:`~tensorflowonspark_tpu.ops.flash_blocks`): the flash
    paths hand both to the kernels, ``plain`` writes the mask out, and the
    ring path, whose blocks know the causal rule alone, refuses it.
    ``rule="window"`` with ``window`` (a static integer) is the third: causal,
    and at most ``window - 1`` positions back; the same three answers.
    """
    if impl not in _ATTENTION_IMPLS:
        raise ValueError(
            "unknown attention impl {!r}; expected one of {}".format(impl, _ATTENTION_IMPLS)
        )
    if rule not in flash_blocks.RULES:
        raise ValueError("unknown attention rule {!r}; expected one of {}".format(rule, flash_blocks.RULES))

    def plain(q, k, v):
        if rule == "block_diffusion":
            return _masked_attention(q, k, v, flash_blocks.bd_mask(segment_ids, labels, xp=jnp), scale)
        if rule == "window":
            ids = jnp.ones((q.shape[0], q.shape[2]), jnp.int32) if segment_ids is None else segment_ids
            return _masked_attention(q, k, v, flash_blocks.window_mask(ids, window, xp=jnp), scale)
        group = q.shape[1] // k.shape[1]
        if group > 1:
            k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        return plain_attention(q, k, v, causal=True, scale=scale, segment_ids=segment_ids)

    if impl == "plain":
        return plain(q, k, v)
    has_sp = mesh is not None and "sp" in mesh.axis_names
    if impl == "ring" or (impl == "auto" and has_sp):
        if rule != "causal" or k.shape[1] != q.shape[1]:
            raise ValueError("ring attention knows the causal rule and one key/value head a query head")
        return ring_attention_sharded(q, k, v, mesh, causal=True, scale=scale, segment_ids=segment_ids)
    if impl == "flash_interpret":
        return _flash(q, k, v, segment_ids, mesh, interpret=True, scale=scale, rule=rule, labels=labels, window=window)
    on_tpu = jax.default_backend() == "tpu"
    if impl == "flash" and not on_tpu:
        raise RuntimeError(
            "attention='flash' needs a TPU backend (got {!r}); use "
            "'flash_interpret' to run the kernel in the Pallas "
            "interpreter, or 'auto'/'plain'".format(jax.default_backend())
        )
    if on_tpu and (impl == "flash" or q.shape[2] >= _FLASH_MIN_SEQ):
        return _flash(q, k, v, segment_ids, mesh, interpret=False, scale=scale, rule=rule, labels=labels, window=window)
    return plain(q, k, v)


class Attention(nn.Module):
    cfg: TransformerConfig
    mesh: object = None  # jax.sharding.Mesh or None

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        dt = cfg.compute_dtype
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            (cfg.n_heads, cfg.head_dim), axis=-1, use_bias=False, dtype=dt, name=name
        )
        # named as the projections return them: what a recomputed block keeps (REMAT_POLICY)
        q, k, v = (checkpoint_name(dense(name)(x), KEPT_PROJECTED) for name in ("q", "k", "v"))  # [B, L, H, D]
        q = _rope(q, positions)
        k = _rope(k, positions)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # [B, H, L, D]
        out = _dispatch_attention(q, k, v, cfg.attention, self.mesh, segment_ids=segment_ids)
        out = out.transpose(0, 2, 1, 3)  # [B, L, H, D]
        return checkpoint_name(nn.DenseGeneral(
            cfg.d_model, axis=(-2, -1), use_bias=False, dtype=dt, name="o"
        )(out), KEPT_ATTENDED)


class Mlp(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        dt = self.cfg.compute_dtype
        h = nn.Dense(self.cfg.d_ff, use_bias=False, dtype=dt, name="wi")(x)
        h = nn.gelu(h)
        return nn.Dense(self.cfg.d_model, use_bias=False, dtype=dt, name="wo")(h)


class Block(nn.Module):
    cfg: TransformerConfig
    mesh: object = None

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        x = x + Attention(self.cfg, self.mesh, name="attn")(
            nn.RMSNorm(dtype=self.cfg.compute_dtype, name="ln1")(x), positions,
            segment_ids,
        )
        x = x + Mlp(self.cfg, name="mlp")(
            nn.RMSNorm(dtype=self.cfg.compute_dtype, name="ln2")(x)
        )
        return x


class Transformer(nn.Module):
    cfg: TransformerConfig
    mesh: object = None

    def _constrain(self, x):
        """Keep activations sharded batch×seq across the mesh. An axis whose
        size does not divide its dim is dropped (degrade-to-replicated, same
        contract as :func:`param_specs`) — real text slabs may carry any
        sequence length; ring attention pads internally."""
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P

        names = self.mesh.axis_names
        sizes = dict(zip(names, self.mesh.devices.shape))
        batch = _batch_axes(self.mesh, x.shape[0])
        seq = "sp" if "sp" in names and x.shape[1] % sizes["sp"] == 0 else None
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(batch, seq, None))
        )

    @nn.compact
    def __call__(self, tokens, positions=None, segment_ids=None):
        cfg = self.cfg
        x = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.compute_dtype, name="embed"
        )(tokens)
        x = self._constrain(x)
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1])[None, :], tokens.shape
            )
        block = Block
        if cfg.remat:
            block = nn.remat(Block, static_argnums=(), policy=REMAT_POLICY)
        for i in range(cfg.n_layers):
            x = block(cfg, self.mesh, name="layer_{}".format(i))(
                x, positions, segment_ids
            )
            x = self._constrain(x)
        x = nn.RMSNorm(dtype=cfg.compute_dtype, name="ln_f")(x)
        logits = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=cfg.compute_dtype, name="lm_head"
        )(x)
        return logits.astype(jnp.float32)


#: path-regex → PartitionSpec-template rules for tensor parallelism; dims not
#: named here fall back to fsdp placement when an fsdp axis exists.
_TP_RULES = (
    (r"attn/(q|k|v)/kernel$", ("fsdp", "tp", None)),  # [d_model, H, head_dim]
    (r"attn/o/kernel$", ("tp", None, "fsdp")),  # [H, head_dim, d_model]
    (r"mlp/wi/kernel$", ("fsdp", "tp")),  # [d_model, d_ff]
    (r"mlp/wo/kernel$", ("tp", "fsdp")),  # [d_ff, d_model]
    # vocab-parallel (Megatron-style): sharding d_model here instead forces
    # XLA to fully rematerialize the gather output to reach the activations'
    # P(batch, seq, None) layout (the round-1 dryrun's SPMD warning); with
    # the vocab dim sharded the gather lowers to masked-lookup + psum
    (r"embed/embedding$", ("fsdp", None)),  # [vocab, d_model]
    (r"lm_head/kernel$", ("fsdp", "tp")),  # [d_model, vocab]
)


def param_specs(params, mesh, tp_axis="tp", rules=_TP_RULES):
    """PartitionSpecs for the transformer's params over ``mesh``: tp rules
    above (or another model's ``rules`` of the same form,
    :mod:`~tensorflowonspark_tpu.models.decoder`'s), fsdp for what they
    leave unnamed, replication for the rest. Axes
    not present in the mesh are dropped from the specs, so the same rules
    serve dp-only, dp×tp, fsdp×sp, etc. ``tp_axis`` renames the mesh axis
    the tensor-parallel dims land on (hybrid meshes sometimes spell it
    differently); the rules themselves always say ``"tp"``. An axis whose
    mesh size does not divide the dim it names is dropped for that dim
    (same degrade-to-replicated contract as the fsdp rules), so undersized
    debug models still place."""
    from jax.sharding import PartitionSpec as P

    names = set(mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    specs = {}
    for path, leaf in flat:
        key = "/".join(
            p.key if hasattr(p, "key") else str(p) for p in path
        )
        spec = None
        for pattern, template in rules:
            if re.search(pattern, key):
                axes = [tp_axis if a == "tp" else a for a in template]
                spec = P(*(
                    a
                    if a in names and leaf.shape[i] % sizes[a] == 0
                    else None
                    for i, a in enumerate(axes)
                ))
                break
        if spec is None:
            spec = P(*([None] * leaf.ndim))
        specs[key] = spec

    def lookup(path, leaf):
        key = "/".join(p.key if hasattr(p, "key") else str(p) for p in path)
        return specs[key]

    return jax.tree_util.tree_map_with_path(lookup, params)


@register("transformer")
def create_model(mesh=None, **cfg):
    return Transformer(TransformerConfig(**cfg), mesh=mesh)


def make_init_fn(model, sample_len=16):
    def init(rng):
        return model.init(rng, jnp.zeros((1, sample_len), jnp.int32))

    return init


def _carried(metrics, mods):
    """What the model counted in this step, into the step's ``metrics``,
    where TrainStep books it by name once the host has found the step
    finished."""
    for kind in ("counter", "gauge"):
        for name, sown in mods.get(kind + "s", {}).items():
            metrics[kind + "/" + name] = sown[-1]
    return metrics


def make_block_diffusion_loss_fn(model):
    """The block-diffusion objective (a model whose configuration says
    ``objective: block_diffusion``; arXiv:2503.09573, as SDAR trains it):
    batch = the text plane's noised batch, ``{"tokens", "noised_tokens",
    "segment_ids", "positions"}`` ``int32 [B, L]`` and ``"loss_weights"``
    ``float32 [B, L]`` (``1 / t`` of its block at a masked position, else 0).

    The model reads the row ``[x_0 ; x_t]``, 2 L positions, both copies at the
    same rotary positions and ids, labelled ``2 * block + half`` (a document's
    blocks are ``block_length`` positions, counted from its start) for the
    attention's second rule; the head runs on the noised half alone. Loss:
    the weighted cross-entropy of a masked position's logits against its own
    clean token (no shift), summed and divided by the batch's real tokens."""
    block_length = model.cfg.block_length

    def loss_fn(params, batch):
        tokens, seg, pos = batch["tokens"], batch["segment_ids"], batch["positions"]
        length = tokens.shape[1]
        block = pos // block_length
        twice = lambda x: jnp.concatenate([x, x], axis=1)  # noqa: E731
        logits, mods = model.apply(
            {"params": params}, jnp.concatenate([tokens, batch["noised_tokens"]], axis=1),
            positions=twice(pos), segment_ids=twice(seg),
            labels=jnp.concatenate([2 * block, 2 * block + 1], axis=1), head_from=length,
            mutable=["counters", "gauges"],
        )
        losses = optax.softmax_cross_entropy_with_integer_labels(logits, tokens)
        weights = batch["loss_weights"].astype(losses.dtype)
        loss = (losses * weights).sum() / jnp.maximum((seg > 0).sum(), 1)
        return loss, _carried({"masked_positions": (weights > 0).sum()}, mods)

    return loss_fn


def make_loss_fn(model):
    """The model's objective: next-token unless its configuration says
    ``objective: block_diffusion`` (:func:`make_block_diffusion_loss_fn`).

    Next-token LM loss; batch = {"tokens": int32 [B, L]} (optionally with
    {"mask": [B, L]} to exclude padding).

    Packed batches from the text plane additionally carry ``segment_ids``
    and ``positions`` (``int32 [B, L]``): segments fence attention
    block-diagonally, per-segment positions keep the rotary phase local,
    and the loss drops targets that cross a pack boundary (the last token
    of one sequence must not be asked to predict the first of the next) or
    fall in padding."""

    if getattr(model.cfg, "objective", "next_token") == "block_diffusion":
        return make_block_diffusion_loss_fn(model)

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        seg = batch.get("segment_ids")
        pos = batch.get("positions")
        logits, mods = model.apply(
            {"params": params}, tokens[:, :-1],
            positions=None if pos is None else pos[:, :-1],
            segment_ids=None if seg is None else seg[:, :-1],
            mutable=["counters", "gauges"],
        )
        targets = tokens[:, 1:]
        losses = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        mask = batch.get("mask")
        mask = None if mask is None else mask[:, 1:]
        if seg is not None:
            # a target is valid when its position and the position it is
            # predicted from share a real (non-pad) segment — the last token
            # of one packed sequence never predicts the first of the next
            seg_mask = ((seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] > 0)).astype(
                losses.dtype
            )
            mask = seg_mask if mask is None else mask * seg_mask
        if mask is not None:
            loss = (losses * mask).sum() / jnp.maximum(mask.sum(), 1)
        else:
            loss = losses.mean()
        return loss, _carried({"perplexity": jnp.exp(loss)}, mods)

    return loss_fn
