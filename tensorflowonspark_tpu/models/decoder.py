"""Decoder LMs assembled from a per-layer plan.

:mod:`~tensorflowonspark_tpu.models.transformer` is one block repeated; the
open models of 2025-26 are not. Here a model is a list of layers, and a layer
names three kinds — its mixer (an attention, a state-space layer, a gated
memory unit), its feed-forward and its residual path — each a small module
of its own with its own placement rules; a layer may name no mixer, or no
feed-forward, and is then **a block of one sub-layer**:

===========  ==========  ======================================================
part         kind        module
===========  ==========  ======================================================
mixer        ``mla``     :class:`LatentAttention`: low-rank query and key/value
                         paths, one rotary key shared by all heads (YaRN
                         frequencies), queries and keys wider than values
mixer        ``gqa``     :class:`GroupedQueryAttention`: q/k/v/o projections,
                         ``num_key_value_heads`` key/value heads each read by
                         a group of query heads, an RMSNorm with a learned
                         weight on every head of q and k (``qk_norm``), rotary
                         positions over the whole head or, by the layer's
                         type, YaRN's over a part of it (halves rotated
                         together), optionally a sigmoid gate a head on the
                         heads' outputs (``gating: per-head``); where ``layer_types`` says
                         ``sliding_attention`` the layer has its own head
                         count (``num_attention_heads_per_layer``), its own
                         rotary (``rope_parameters``) and the attention's
                         third rule, causal within ``sliding_window``
                         positions: the layer's :class:`HeadsPlan`
mixer        ``gqa``     … in **differential form** where the layer's plan says
                         ``lambda_init`` (two softmax maps a query pair,
                         subtracted, a norm over the pair: the class's text),
                         with biases (``attention_bias``) and without rotary
                         positions (``rotary`` false) where the configuration
                         says so
mixer        ``cross``   the same module reading the keys and values an
                         earlier ``gqa`` layer handed on: a query projection
                         and no key or value projection of its own
mixer        ``mamba``   :class:`MambaMixer`: in projection, short causal
                         convolution, low-rank step and state projections,
                         the selective scan of ``ops/selective_scan.py``
                         (float32 state, restarting at every document of a
                         packed row), a SiLU gate, out projection; its scan
                         output is handed on where the plan says
mixer        ``gmu``     :class:`GatedMemory`: ``(silu(u W_1) * m) W_2``, ``m``
                         the scan output an earlier ``mamba`` layer handed on
mixer        ``mamba2``  :class:`Mamba2Mixer`: one in projection to ``[z | xBC
                         | dt]``, the short causal convolution over ``xBC``,
                         heads of ``mamba_head_dim`` channels with one scalar
                         decay each, in groups that share their ``B`` and
                         ``C``, scanned in chunked matrix form
                         (``ops/ssd_scan.py``: three products a chunk on the
                         matrix unit, only the chunks' states carried;
                         float32 decays and state, restarting at every
                         document of a packed row), the gated norm a group
                         (gate first), out projection
mixer        None        no mixer: the block is its feed-forward alone
feed-forward ``swiglu``  :class:`SwiGLU`: the dense gated MLP
feed-forward ``moe``     :class:`RoutedExperts`: scores (``scoring_func``:
                         ``sigmoid`` with a selection bias, ``noaux_tc``; or
                         ``softmax`` over all experts, no bias), top-k, the
                         chosen scores renormalised and scaled, the experts
                         *held here*
                         applied to the slots routed to them (nothing
                         dropped), beside shared experts, if any, that see
                         every token; under ``moe_latent_size`` the routed
                         experts **work in a latent** (a projection down
                         before the dispatch and one up after the combine: the
                         slot buffer, the row gathers, the combine kernel and
                         the grouped products all run that wide; the router
                         and the shared expert read the model's width); under
                         ``mlp_hidden_act: relu2`` an expert, the shared one
                         too, is two matrices and no gate, ``relu(x W_up)^2
                         W_down``
feed-forward None        no feed-forward: the block is its mixer alone
residual     ``add``     ``x + F(norm(x))``
residual     ``mhc``     :class:`HyperConnection`: ``hc_mult`` residual
                         streams, three learned maps per sub-layer, the
                         stream-mixing map projected onto doubly stochastic
                         matrices by Sinkhorn iterations (manifold-constrained
                         hyper-connections, arXiv:2512.24880); the streams are
                         carried as ``[B, L, hc_mult * d]`` and read twice a
                         sub-layer each way (``ops/hyper_connection.py``)
===========  ==========  ======================================================

The configuration is a dict with the published ``config.json``'s keys
(:class:`DecoderConfig`), in one of four dialects: the one that says
``n_routed_experts`` (latent attention where ``kv_lora_rank`` is given,
sigmoid ``noaux_tc`` routing), the one that says ``num_experts`` (``gqa``,
softmax routing without a bias; ``decoder_sparse_step`` 1) and the one that
says ``mb_per_layer`` (the decoder-hybrid-decoder of arXiv:2507.06607,
``model_type`` ``phi4flash``: with ``N`` layers, every even layer up to ``N /
2`` is ``mamba`` and every even layer after it ``gmu``, every odd layer up to
``N / 2 + 1`` differential ``gqa`` — within ``sliding_window`` before ``N /
2``, the whole document at ``N / 2 + 1`` — and every odd layer after it
``cross``; layer ``N / 2``'s scan output and layer ``N / 2 + 1``'s keys and
values are handed on; LayerNorm at ``layer_norm_eps``, no positional
encoding, biases on the attention's projections; ``first_layer`` and
``model_layers`` say which of the published layers are held here, so that a
cut in depth keeps each layer's own kind and ``lambda_init``;
``tie_word_embeddings`` is read in every dialect: true shares the embedding's
matrix with the head) and the one that says ``hybrid_override_pattern``
(``model_type`` ``nemotron_h``: a letter a block of the published model,
``M`` a ``mamba2`` mixer, ``E`` routed experts, ``*`` ``gqa``, **each block
one sub-layer** ``x + F(norm(x))``; RMSNorm at ``layer_norm_epsilon``, no
positional encoding and no norm on q's and k's heads, sigmoid routing with a
selection bias, ``moe_latent_size``, ``mlp_hidden_act`` and
``moe_shared_expert_intermediate_size`` read as above, ``first_layer`` and
``model_layers`` saying which of the published blocks are held here; a dense
block ``-`` and the prediction module are not built, and ``moe_latent_size``
or ``mlp_hidden_act: relu2`` in another dialect is refused by name, since its
layers would ignore them). The second may
say more, layer by layer: ``layer_types`` (``full_attention`` /
``sliding_attention`` with ``sliding_window``), ``num_attention_heads_per_layer``,
``rope_parameters`` by layer type (``rope_type`` ``default`` or ``yarn``,
``partial_rotary_factor``), ``gating`` (``per-head`` is the one implemented),
``mlp_only_layers`` / ``mlp_layer_types`` (which layers are dense),
``shared_expert_intermediate_size`` and ``moe_routed_scaling_factor``; a list a
layer is read as far as ``num_hidden_layers``, so a cut in depth keeps the
published lists. ``layer_plan`` lists the layers' kinds and defaults to the
published pattern: ``first_k_dense_replace`` (or the named) dense layers and
routed ones after, with ``mla`` where the configuration has a
``kv_lora_rank`` and ``gqa`` otherwise; what differs from one ``gqa`` layer
to the next (head count, window, rotary, gate) is the layer's
:class:`HeadsPlan` (:meth:`DecoderConfig.heads_plan`, from ``layer_types``
and the lists beside it).

``objective`` is ``next_token`` or ``block_diffusion`` (with ``block_length``
and ``mask_token_id``: a published configuration gives neither, so the job
does): ``transformer.make_loss_fn`` then builds that loss, and the model is
called with ``labels`` (the attention's second rule:
:mod:`~tensorflowonspark_tpu.ops.flash_blocks`) and ``head_from`` (the first
position the head is run on: the noised half of a row that holds a clean
and a noised copy). A chip
that holds a share of a layer's experts says which (``experts_held``:
first, count): the router stays as wide as the model's, and the layer adds
only its own experts' terms — what expert parallelism asks of a layer, here
without the exchange. One that holds a share of every mixer's heads says
which (``heads_held``: index, shares): a ``mamba2`` mixer then holds that
share of its heads in whole groups (so the grouped norm is exact), a ``gqa``
layer that share of its query heads with the key/value heads they read (one
head where the shares outnumber the key/value heads: the chips that hold its
readers each hold it), each builds its projections that wide and returns
**its heads' part of the output projection's sum** — what tensor parallelism
asks of a mixer, here without the all-reduce: nothing stands in for the other
shares' parts.

Compute is ``dtype`` (bfloat16 on the chip) on float32 parameters; the
router's scores, the hyper-connection maps, the Sinkhorn iterations and the
softmax statistics are float32. The model plugs into
``transformer.make_init_fn`` / ``make_loss_fn`` and ``SyncDataParallel`` as
the dense LM does (``models.get_model("decoder", **config)``).
``remat=True`` recomputes each layer in the backward pass from what the layer
keeps (:data:`~tensorflowonspark_tpu.ops.flash_attention.REMAT_POLICY`), in
bfloat16 a token and layer: its input (the streams, 2 · hc_mult · hidden_size
bytes), the attention's output (2 · heads · v_head_dim, or heads · head_dim)
and one float32 a position and head, so the flash forward kernel runs once a
layer; and the attention sub-layer's products, named where the modules
compute them: under ``gqa`` the q, k and v projections' results as the
projections return them, before any head norm (2 · (heads + 2 · kv heads) ·
head_dim bytes: 10,240 in ``sdar-30b-a3b``, 22,528 in a 72-head layer of
``laguna-s-2-1``), under ``mla`` the two latents (2 · (q_lora_rank +
kv_lora_rank + qk_rope_head_dim): 2688 in ``xing4-a4b``; the up-projections
``q_b`` and ``kv_b`` run again from them), and under both the sub-layer's
result after the output projection (2 · hidden_size). The recomputed pass
runs none of those products: of attention it runs the norms, rotary, the
gate's small product and the transposes, then the feed-forward from its norm
on; the hyper-connections' reading is computed again. A job that fitted its
chip by less than these bytes fails at compile time with XLA's out-of-memory
message.

Device scopes (``jax.named_scope``, in every operation's ``op_name``):
``tos.mla``, ``tos.gqa`` (a windowed layer's attention: ``tos.swa``), ``tos.attn_gate``
inside both, ``tos.moe_route`` (router, top-k, sort, gather, combine),
``tos.moe_experts`` (the grouped products), ``tos.moe_shared``,
``tos.dense_mlp``, ``tos.mhc``; ``tos.mamba`` with ``tos.ssm_conv`` and
``tos.ssm_scan`` (both rules of the scan's ``custom_vjp``) inside it,
``tos.gmu``, ``tos.cross_attn``, and ``tos.diff_attn`` (lambda, the
subtraction and the sub-norm) inside ``tos.gqa`` / ``tos.swa`` /
``tos.cross_attn``; ``tos.mamba2`` with ``tos.ssm_conv`` and ``tos.ssd_scan``
(both rules of that scan's ``custom_vjp``, and what XLA does round them)
inside it; ``tos.moe_latent`` (both latent projections). **What crosses layers beside the residual streams** (a
``mamba`` layer's scan output, a ``gqa`` layer's keys and values, where
:class:`HeadsPlan` says ``hands_on``) leaves the layer that makes it as a
result and enters its readers as an argument (:class:`DecoderLayer`'s third
result and last argument): under ``remat`` it is kept once and no reader
computes it again; its bytes a step are sown as ``ssm_state_carried_bytes``
(``ssm_state_carried_bytes_total``); the chunks the ``mamba2`` layers' scans
walk a step as ``ssd_scan_chunks`` (``ssd_scan_chunks_total``). What the routed layers count in a step is
sown into the ``counters`` collection (``moe_slots_routed``,
``moe_slots_held``; where a chip holds under half the experts also
``moe_layers_compact`` and ``moe_layers_at_bound``: the layers that ran on
the compact slot buffer and those that fell back to the whole one, and
``moe_combine_rows_fetched``: the buffer rows one call of the way back to
token order brings in, a layer on the compact buffer, which over
``moe_slots_held`` is how many times over that kernel reads what it needs)
and the ``gauges`` collection (``moe_expert_load_max_over_mean``), and
registered where it is sown (``moe_slots_routed_total``,
``moe_slots_held_total``, ``moe_layers_compact_total``,
``moe_layers_at_bound_total``, ``moe_combine_rows_fetched_total``, the gauge);
``make_loss_fn`` carries both out in the step's metrics and
:class:`~tensorflowonspark_tpu.train.TrainStep` books them by name.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from tensorflowonspark_tpu import obs
from tensorflowonspark_tpu.models import register, transformer
from tensorflowonspark_tpu.ops import grouped_matmul as gm
from tensorflowonspark_tpu.ops import hyper_connection, moe_combine, selective_scan, ssd_scan
from tensorflowonspark_tpu.ops.flash_attention import KEPT_ATTENDED, KEPT_LSE, KEPT_O, KEPT_PROJECTED

#: what a recomputed layer keeps: ``ops.flash_attention.REMAT_POLICY``'s four names and either scan's two results
REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    KEPT_O, KEPT_LSE, KEPT_PROJECTED, KEPT_ATTENDED, selective_scan.KEPT_SCANNED, selective_scan.KEPT_SCAN_STATE,
    ssd_scan.KEPT_SCANNED, ssd_scan.KEPT_STATE)

#: a state-space layer's sizes, the family's (its published configuration names none): channels a hidden
#: unit, states a channel, the short convolution's taps
MAMBA_EXPAND, MAMBA_STATES, MAMBA_TAPS = 2, 16, 4

#: a layer's first sub-layer, its mixer (the name under which ``layer_plan`` gives it stays "attention"), and
#: its second; None: the block has no such sub-layer (one of the two at most)
MIXER_KINDS = ("mla", "gqa", "mamba", "gmu", "cross", "mamba2", None)
FEED_FORWARD_KINDS = ("swiglu", "moe", None)
#: ``hybrid_override_pattern``'s letters: a block is one sub-layer
_PATTERN_KINDS = {"M": ("mamba2", None, "add"), "E": (None, "moe", "add"), "*": ("gqa", None, "add")}
#: the feed-forwards' forms by ``mlp_hidden_act``: ``silu`` gated (``down(silu(gate x) * up x)``), ``relu2`` two
#: matrices and no gate (``down(relu(up x) ** 2)``)
MLP_ACTS = ("silu", "relu2")
RESIDUAL_KINDS = ("add", "mhc")

#: keys of a published ``config.json`` that say nothing this module computes
#: from, and the values the ones it does not implement must have
_IGNORED_KEYS = (
    "model_type", "ep_size", "moe_layer_freq", "max_position_embeddings", "num_nextn_predict_layers",
    "max_window_layers",
)
_REQUIRED_VALUES = {
    "hidden_act": "silu", "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "use_sliding_window": False, "moe_router_logit_softcapping": 0,
    "moe_apply_router_weight_on_input": False, "embd_pdrop": 0, "resid_pdrop": 0, "mlp_bias": False,
    "lm_head_bias": False,
}
LAYER_TYPES = ("full_attention", "sliding_attention")
ROPE_TYPES = ("default", "yarn")
#: the keys a published config may give a layer's rotary positions by
_ROPE_KEYS = {
    "rope_type", "rope_theta", "partial_rotary_factor", "factor", "original_max_position_embeddings", "beta_fast",
    "beta_slow", "attention_factor",
}
#: the routing each dialect's ``scoring_func`` goes with
_TOPK_METHODS = {"sigmoid": "noaux_tc", "softmax": "greedy"}
OBJECTIVES = ("next_token", "block_diffusion")


@dataclasses.dataclass(frozen=True)
class HeadsPlan:
    """What one layer's mixer is where the layers differ: a grouped-query
    attention's head count, window, rotary, gate and form; of any mixer,
    whether later layers read what it makes."""

    heads: int
    #: the third rule's window; None: the whole document
    window: int = None
    #: the layer type's ``rope_parameters`` as sorted items; (): ``rope_theta`` over the whole head
    rope: tuple = ()
    #: a sigmoid gate a head on the heads' outputs
    gate: bool = False
    #: differential attention's ``lambda_init`` of this layer; None: one softmax map a head
    lambda_init: float = None
    #: later layers read what this one makes (a ``mamba`` layer's scan output, a ``gqa`` layer's keys and values)
    hands_on: bool = False


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    # latent attention (``mla``)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # grouped-query attention (``gqa``); ``mla`` reads none of these
    num_key_value_heads: int = 0
    head_dim: int = 0
    #: an RMSNorm with a learned weight on every head of q and of k
    qk_norm: bool = True
    #: per layer ``full_attention`` / ``sliding_attention``; (): every layer full
    layer_types: tuple = ()
    sliding_window: int = None
    #: per layer; (): ``num_attention_heads`` everywhere
    num_attention_heads_per_layer: tuple = ()
    #: ((layer type, the type's rotary parameters as sorted items), …)
    rope_parameters: tuple = ()
    #: ``per-head``: a sigmoid gate a head on the attention's output; None: none
    gating: str = None
    rope_theta: float = 10000.0
    #: the ``rope_scaling`` dict (``type: yarn``) as sorted items, or ()
    rope_scaling: tuple = ()
    # feed-forward
    intermediate_size: int = 0
    moe_intermediate_size: int = 0
    #: the router's width: every expert of the model, held here or not
    n_routed_experts: int = 0
    #: (first, count) of the routed experts this chip holds; None = all
    experts_held: tuple = None
    num_experts_per_tok: int = 0
    #: ``sigmoid`` (top-k of the biased scores) or ``softmax`` (no bias)
    scoring_func: str = "sigmoid"
    n_shared_experts: int = 0
    #: the shared expert's width where the configuration names it (else
    #: ``n_shared_experts`` times the routed experts')
    shared_expert_intermediate_size: int = 0
    routed_scaling_factor: float = 1.0
    #: padding positions (segment id 0) are routed like any token; False: they
    #: take no expert's slot (a packed row's padding is one token at one
    #: position: it all goes to the same experts, and where one is held here
    #: the layer's held slots pass the compact slot buffer)
    padding_slots: bool = True
    first_k_dense_replace: int = 0
    #: the layers that are dense whatever their place (with the first
    #: ``first_k_dense_replace``)
    mlp_only_layers: tuple = ()
    # residual path
    # the decoder-hybrid-decoder dialect (``mb_per_layer``): every ``mb_per_layer``-th layer a state-space or
    # gated-memory mixer, the others differential attention, the second half reading what the first half's last
    # two layers made
    mb_per_layer: int = 0
    #: the place in the published model of the first layer held here, and the published depth (None:
    #: ``num_hidden_layers``): a cut in depth keeps each layer's own kind and ``lambda_init``
    first_layer: int = 0
    model_layers: int = None
    # the one-sub-layer dialect (``hybrid_override_pattern``, ``model_type`` ``nemotron_h``): a letter a block of the
    # published model, ``M`` a Mamba-2 mixer, ``E`` routed experts, ``*`` grouped-query attention; the blocks held
    # here are ``first_layer`` .. ``first_layer + num_hidden_layers``
    hybrid_override_pattern: str = ""
    # the Mamba-2 mixer (``mamba2``): heads of ``mamba_head_dim`` channels, a scalar decay each, in ``n_groups``
    # groups that share their state projections of ``ssm_state_size``; scanned in chunks of ``chunk_size``
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    n_groups: int = 1
    conv_kernel: int = 4
    chunk_size: int = ssd_scan.DEFAULT_CHUNK
    #: the step's bias is seeded as the inverse softplus of a step log-uniform in [min, max], floored (init only)
    time_step_min: float = 1e-3
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    #: (index, shares): of every mixer's heads (a Mamba-2 mixer's groups, an attention's key/value heads with
    #: them) this chip holds share ``index`` of ``shares`` equal ones; None = all. The mixer builds its
    #: projections that wide and returns its heads' part of the output projection's sum
    heads_held: tuple = None
    #: the routed experts work in a latent of this width (a projection down before the dispatch, one up after
    #: the combine; the router and the shared expert read the model's width); 0: at ``hidden_size``
    moe_latent_size: int = 0
    #: the routed and shared experts' form, one of :data:`MLP_ACTS`
    mlp_hidden_act: str = "silu"
    #: LayerNorm (weight and bias) at this epsilon in place of RMSNorm; None: RMSNorm at ``rms_norm_eps``
    layer_norm_eps: float = None
    #: biases on the attention's projections (q, k, v and the output's)
    attention_bias: bool = False
    #: the head is the embedding's matrix (there is no ``lm_head`` parameter)
    tie_word_embeddings: bool = False
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    rms_norm_eps: float = 1e-6
    #: ((attention, feed-forward, residual), …), one entry per layer
    layer_plan: tuple = None
    dtype: str = "float32"  # compute dtype; params stay float32
    #: recompute each layer in the backward pass: a layer keeps its input (the
    #: streams), the attention's output and one float32 a position and head
    remat: bool = False
    attention: str = "auto"  # transformer._dispatch_attention's choices
    #: what ``transformer.make_loss_fn`` trains: the next token, or diffusion
    #: over blocks of ``block_length`` positions with ``mask_token_id`` the
    #: noise (None: the vocabulary's last id)
    objective: str = "next_token"
    block_length: int = 4
    mask_token_id: int = None

    @classmethod
    def from_dict(cls, cfg):
        cfg = dict(cfg)
        for key in _IGNORED_KEYS:
            cfg.pop(key, None)
        for key, want in _REQUIRED_VALUES.items():
            if cfg.pop(key, want) != want:
                raise ValueError("decoder: {} must be {!r}".format(key, want))
        if "num_experts" in cfg:  # the dialect of softmax routers: no scoring_func key, no bias
            cfg["n_routed_experts"] = cfg.pop("num_experts")
            cfg.setdefault("scoring_func", "softmax")
        if "moe_routed_scaling_factor" in cfg:
            cfg["routed_scaling_factor"] = cfg.pop("moe_routed_scaling_factor")
        if "mb_per_layer" in cfg:
            _hybrid_dialect(cfg)
        if "hybrid_override_pattern" in cfg:
            _pattern_dialect(cfg)
        else:
            # read by the one-sub-layer dialect's experts alone: anywhere else they would change nothing
            for key, idle in (("moe_latent_size", 0), ("mlp_hidden_act", "silu")):
                if cfg.get(key, idle) != idle:
                    raise ValueError(
                        "decoder: {} {!r} is implemented in the hybrid_override_pattern dialect only; this "
                        "configuration's layers would ignore it".format(key, cfg[key]))
        _per_layer(cfg, cfg.get("num_hidden_layers", 0))
        scoring = cfg.setdefault("scoring_func", "sigmoid")
        if scoring not in _TOPK_METHODS or cfg.pop("topk_method", _TOPK_METHODS[scoring]) != _TOPK_METHODS[scoring]:
            raise ValueError("decoder: scoring_func/topk_method must be one of {}".format(sorted(_TOPK_METHODS.items())))
        if cfg.get("objective", "next_token") not in OBJECTIVES:
            raise ValueError("decoder: objective must be one of {}".format(OBJECTIVES))
        scaling = cfg.pop("rope_scaling", None) or {}
        if scaling and scaling.get("type") != "yarn":
            raise ValueError("decoder: rope_scaling type {!r} is not implemented".format(scaling.get("type")))
        cfg["rope_scaling"] = tuple(sorted(scaling.items()))
        for key in ("experts_held", "heads_held"):
            if cfg.get(key) is not None:
                cfg[key] = tuple(cfg[key])
        if cfg.get("heads_held") is not None and cfg.get("attention_bias"):
            raise ValueError("decoder: heads_held with attention_bias would add the output's bias once a share")
        if cfg.get("layer_plan") is not None:
            cfg["layer_plan"] = tuple(tuple(layer) for layer in cfg["layer_plan"])
        if cfg.get("mlp_hidden_act", "silu") not in MLP_ACTS:
            raise ValueError("decoder: mlp_hidden_act must be one of {}".format(MLP_ACTS))
        cfg["rope_parameters"] = _rope_parameters(cfg.pop("rope_parameters", None) or {})
        unknown = set(cfg) - {f.name for f in dataclasses.fields(cls)}
        if unknown:
            raise ValueError("decoder: unknown configuration keys {}".format(sorted(unknown)))
        return cls(**cfg)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def plan(self):
        """The layers' kinds: ``layer_plan``, or the published pattern —
        ``first_k_dense_replace`` dense layers, then routed ones."""
        if self.layer_plan is not None:
            plan = self.layer_plan
        elif self.hybrid_override_pattern:
            plan = tuple(_PATTERN_KINDS[letter] for letter in self.hybrid_override_pattern[
                self.first_layer:self.first_layer + self.num_hidden_layers])
        elif self.mb_per_layer:
            half = self.depth // 2
            plan = tuple(
                (("mamba" if at <= half else "gmu") if at % self.mb_per_layer == 0
                 else ("gqa" if at <= half + 1 else "cross"), "swiglu", "add")
                for at in range(self.first_layer, self.first_layer + self.num_hidden_layers))
        else:
            residual = "mhc" if self.hc_mult > 1 else "add"
            plan = tuple(
                ("mla" if self.kv_lora_rank else "gqa",
                 "swiglu" if (i < self.first_k_dense_replace or i in self.mlp_only_layers
                              or not self.n_routed_experts) else "moe", residual)
                for i in range(self.num_hidden_layers))
        if len(plan) != self.num_hidden_layers:
            raise ValueError("decoder: layer_plan has {} layers, num_hidden_layers is {}".format(
                len(plan), self.num_hidden_layers))
        for attention, feed_forward, residual in plan:
            if (attention not in MIXER_KINDS or feed_forward not in FEED_FORWARD_KINDS
                    or residual not in RESIDUAL_KINDS or (attention is None and feed_forward is None)):
                raise ValueError("decoder: unknown layer kinds {}".format((attention, feed_forward, residual)))
            if residual == "add" and self.hc_mult != 1:
                raise ValueError("decoder: an 'add' residual carries one stream (hc_mult 1)")
        for reader, maker in (("gmu", "mamba"), ("cross", "gqa")):
            kinds = [layer[0] for layer in plan]
            made = [i for i, kind in enumerate(kinds) if kind == maker and self.heads_plan(i).hands_on]
            if reader in kinds and (not made or kinds.index(reader) < made[0]):
                raise ValueError(
                    "decoder: a {!r} layer reads what a {!r} layer before it hands on, and none of the layers held "
                    "here does (first_layer {}, {} layers of {})".format(
                        reader, maker, self.first_layer, self.num_hidden_layers, self.depth))
        return plan

    @property
    def depth(self):
        """The published model's layers (of which ``num_hidden_layers`` are held here)."""
        return self.model_layers or self.num_hidden_layers

    def heads_plan(self, index):
        """Layer ``index``'s :class:`HeadsPlan` (kind ``gqa``)."""
        windowed = bool(self.layer_types) and self.layer_types[index] == "sliding_attention"
        per_layer = self.num_attention_heads_per_layer
        at = self.first_layer + index
        return HeadsPlan(
            heads=self.share_of(per_layer[index] if per_layer else self.num_attention_heads, "attention heads"),
            window=self.sliding_window if windowed else None,
            rope=dict(self.rope_parameters).get(LAYER_TYPES[windowed], ()),
            gate=self.gating is not None,
            # the family's rule: 0.8 - 0.6 exp(-0.3 l), l the layer's place in the published model
            lambda_init=0.8 - 0.6 * math.exp(-0.3 * at) if self.mb_per_layer else None,
            # the first half's last state-space layer and the one full-attention layer after it
            hands_on=bool(self.mb_per_layer) and at in (self.depth // 2, self.depth // 2 + 1))

    @property
    def rotary(self):
        """Rotary positions on q and k; the ``mb_per_layer`` and ``hybrid_override_pattern`` dialects have no
        positional encoding at all."""
        return not (self.mb_per_layer or self.hybrid_override_pattern)

    @property
    def share(self):
        """``heads_held``, or the one share of one: (index, shares)."""
        return self.heads_held or (0, 1)

    def share_of(self, count, what):
        """How many of a mixer's ``count`` heads (or groups) are held here (``heads_held``)."""
        index, shares = self.share
        if not 0 <= index < shares or count % shares:
            raise ValueError("decoder: heads_held {} does not divide {} {}".format(self.heads_held, count, what))
        return count // shares

    @property
    def kv_heads(self):
        """The key/value heads a ``gqa`` layer holds here: its share of ``num_key_value_heads``, or one head
        where the shares outnumber them (the chips that hold its readers each hold the head)."""
        shares = self.share[1]
        if self.num_key_value_heads and shares > self.num_key_value_heads:
            if shares % self.num_key_value_heads:
                raise ValueError("decoder: heads_held {} and {} key/value heads: neither divides the other".format(
                    self.heads_held, self.num_key_value_heads))
            return 1
        return self.share_of(self.num_key_value_heads, "key/value heads")

    @property
    def dt_rank(self):
        """The rank of a state-space layer's step projection: the family's ``ceil(hidden_size / 16)``."""
        return -(-self.hidden_size // 16)

    @property
    def d_inner(self):
        return MAMBA_EXPAND * self.hidden_size

    @property
    def shared_width(self):
        """The shared expert's width; 0: the routed layers have none."""
        return self.shared_expert_intermediate_size or self.moe_intermediate_size * self.n_shared_experts

    @property
    def mask_id(self):
        return self.vocab_size - 1 if self.mask_token_id is None else self.mask_token_id

    @property
    def held(self):
        """(first, count) of the routed experts held here."""
        return self.experts_held if self.experts_held is not None else (0, self.n_routed_experts)


def _hybrid_dialect(cfg):
    """The keys of a published ``mb_per_layer`` configuration (``model_type``
    ``phi4flash``: the decoder-hybrid-decoder of arXiv:2507.06607) as this
    module reads them, in place: what the family's code fixes and the file
    does not say (no norm on q's and k's heads, biases on the attention's
    projections, no positional encoding), and ``layer_types`` for the layers
    held here: the odd layers of the first half attend within
    ``sliding_window``."""
    if cfg["mb_per_layer"] != 2:
        raise ValueError("decoder: mb_per_layer {!r} is not implemented (2 is)".format(cfg["mb_per_layer"]))
    for key, value in (("qk_norm", False), ("attention_bias", True)):
        cfg.setdefault(key, value)
    first, held = cfg.get("first_layer", 0), cfg.get("num_hidden_layers", 0)
    half = (cfg.get("model_layers") or held) // 2
    cfg["layer_types"] = [
        "sliding_attention" if at % 2 and at < half and cfg.get("sliding_window") else "full_attention"
        for at in range(first, first + held)]
    if "sliding_attention" not in cfg["layer_types"]:
        cfg.pop("sliding_window", None)  # the layers held here hold none of the windowed ones


#: the one-sub-layer dialect: keys of a published ``nemotron_h`` configuration that say nothing this module
#: computes from (the attention blocks of the family's code read neither ``rope_theta`` nor
#: ``partial_rotary_factor``; the prediction module is not built: ROADMAP M3), and the values the ones it does not
#: implement must have
_PATTERN_IGNORED = (
    "rope_theta", "partial_rotary_factor", "mtp_hybrid_override_pattern", "num_logits_to_keep",
    "rescale_prenorm_residual", "use_mamba_kernels", "intermediate_size",
)
_PATTERN_REQUIRED = {
    "mamba_hidden_act": "silu", "mamba_proj_bias": False, "use_bias": False, "use_conv_bias": True,
    "residual_in_fp32": False, "moe_shared_expert_overlap": False, "sliding_window": None, "n_shared_experts": 1,
}


def _pattern_dialect(cfg):
    """The keys of a published ``hybrid_override_pattern`` configuration
    (``model_type`` ``nemotron_h``) as this module reads them, in place: what
    the family's code fixes and the file does not say (no norm on q's and k's
    heads, no positional encoding), RMSNorm at ``layer_norm_epsilon`` (=
    ``norm_eps``), the shared expert's width under this module's name, and the
    pattern checked: as long as the published model (``model_layers``), of the
    letters this module builds, ``expand`` times the hidden size the Mamba-2
    heads' channels."""
    for key in _PATTERN_IGNORED:
        cfg.pop(key, None)
    for key, want in _PATTERN_REQUIRED.items():
        if cfg.pop(key, want) != want:
            raise ValueError("decoder: {} must be {!r}".format(key, want))
    pattern = cfg["hybrid_override_pattern"]
    depth = cfg.setdefault("model_layers", cfg.get("num_hidden_layers"))
    if len(pattern) != depth or set(pattern) - set(_PATTERN_KINDS):
        raise ValueError("decoder: hybrid_override_pattern must name {} blocks, each one of {} ('-', a dense "
                         "block, is not implemented)".format(depth, sorted(_PATTERN_KINDS)))
    eps = {cfg.pop(key) for key in ("layer_norm_epsilon", "norm_eps") if key in cfg}
    if len(eps) > 1:
        raise ValueError("decoder: layer_norm_epsilon and norm_eps differ: {}".format(sorted(eps)))
    if eps:
        cfg["rms_norm_eps"] = eps.pop()
    if "moe_shared_expert_intermediate_size" in cfg:
        cfg["shared_expert_intermediate_size"] = cfg.pop("moe_shared_expert_intermediate_size")
    inner = cfg.get("mamba_num_heads", 0) * cfg.get("mamba_head_dim", 0)
    if cfg.pop("expand", None) not in (None, inner / cfg["hidden_size"]):
        raise ValueError("decoder: expand times hidden_size is not mamba_num_heads x mamba_head_dim = {}".format(inner))
    cfg.setdefault("qk_norm", False)


def _per_layer(cfg, layers):
    """The keys of a published configuration that say something layer by
    layer, checked and cut to the ``layers`` the model has (in place)."""
    for key, allowed in (("layer_types", LAYER_TYPES), ("gating_types", ("per_head",)),
                         ("mlp_layer_types", ("dense", "sparse"))):
        if cfg.get(key) is not None:
            cfg[key] = tuple(cfg[key][:layers])
            if len(cfg[key]) != layers or set(cfg[key]) - set(allowed):
                raise ValueError("decoder: {} must name {} layers, each one of {}".format(key, layers, allowed))
    if cfg.pop("gating_types", None) is not None:
        cfg.setdefault("gating", "per-head")
    if cfg.get("gating") not in (None, "per-head"):
        raise ValueError("decoder: gating {!r} is not implemented (per-head is)".format(cfg["gating"]))
    if cfg.get("num_attention_heads_per_layer") is not None:
        cfg["num_attention_heads_per_layer"] = tuple(cfg["num_attention_heads_per_layer"][:layers])
        if len(cfg["num_attention_heads_per_layer"]) != layers:
            raise ValueError("decoder: num_attention_heads_per_layer must name {} layers".format(layers))
    dense = tuple(i for i in cfg.get("mlp_only_layers") or () if i < layers)
    by_type = cfg.pop("mlp_layer_types", None)
    if by_type is not None:
        named = tuple(i for i, kind in enumerate(by_type) if kind == "dense")
        if "mlp_only_layers" in cfg and named != dense:
            raise ValueError("decoder: mlp_layer_types and mlp_only_layers name different dense layers")
        dense = named
    cfg["mlp_only_layers"] = dense
    windowed = "sliding_attention" in (cfg.get("layer_types") or ())
    if windowed != bool(cfg.get("sliding_window")):
        raise ValueError("decoder: sliding_window goes with sliding_attention layers in layer_types, and they with it")


def _rope_parameters(by_type):
    """``rope_parameters`` (a dict a layer type) as the hashable the
    configuration keeps, each type's keys and kind checked."""
    out = []
    for kind, params in sorted(by_type.items()):
        if kind not in LAYER_TYPES or set(params) - _ROPE_KEYS or params.get("rope_type", "default") not in ROPE_TYPES:
            raise ValueError("decoder: rope_parameters[{!r}] = {} is not implemented (layer types {}, rope_type {}, "
                             "keys {})".format(kind, params, LAYER_TYPES, ROPE_TYPES, sorted(_ROPE_KEYS)))
        out.append((kind, tuple(sorted(params.items()))))
    return tuple(out)


def yarn_inv_freq(dim, theta, scaling):
    """The rotary part's ``dim // 2`` inverse frequencies. Under YaRN the
    slow ones are divided by ``factor``, the fast ones kept, and those in
    the correction range (between ``beta_fast`` and ``beta_slow`` rotations
    over the original length) blended by a linear ramp."""
    exponents = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    kept = theta ** -exponents
    if not scaling:
        return kept

    def correction_dim(rotations):
        return dim * math.log(scaling["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
    return (kept / scaling["factor"]) * ramp + kept * (1.0 - ramp)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope_interleaved(x, positions, inv_freq, scale):
    """Rotary positions over the last dim of ``x`` ``[B, L, …, D]``, its
    pairs interleaved: (x0, x1), (x2, x3), … rotate together."""
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [B, L, D/2]
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3) + angles.shape[-1:])
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _rope_halves(x, positions, inv_freq, scale):
    """Rotary positions over the first ``2 * len(inv_freq)`` of the last dim
    of ``x`` ``[B, L, H, D]``, that part's halves rotated together, cos and
    sin times ``scale``; the rest of the head passes as it is."""
    half = inv_freq.shape[0]
    angles = positions[:, :, None].astype(jnp.float32) * inv_freq  # [B, L, half]
    cos, sin = (jnp.cos(angles) * scale)[:, :, None, :], (jnp.sin(angles) * scale)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., 2 * half:]], axis=-1).astype(x.dtype)


def _rotary(cfg, rope, width):
    """``rope(t, positions)`` of a ``gqa`` layer whose type's
    ``rope_parameters`` are ``rope`` ({}: the model's ``rope_theta`` over the
    whole head of ``width``)."""
    if not rope:
        return lambda t, positions: transformer._rope(t, positions, cfg.rope_theta)
    yarn = rope.get("rope_type", "default") == "yarn"
    inv_freq = yarn_inv_freq(
        int(width * rope.get("partial_rotary_factor", 1.0)), rope.get("rope_theta", cfg.rope_theta),
        rope if yarn else None)
    scale = rope.get("attention_factor", yarn_mscale(rope["factor"], 1.0)) if yarn else 1.0
    return lambda t, positions: _rope_halves(t, positions, inv_freq, scale)


def _norm(cfg, name):
    if cfg.layer_norm_eps is not None:
        return nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.compute_dtype, name=name)
    return nn.RMSNorm(epsilon=cfg.rms_norm_eps, dtype=cfg.compute_dtype, name=name)


class LatentAttention(nn.Module):
    """Multi-head latent attention as trained: the query through a rank
    ``q_lora_rank`` bottleneck, keys and values up from one normed latent of
    rank ``kv_lora_rank``, and a rotary key of ``qk_rope_head_dim`` that all
    heads share. Heads attend with queries and keys of width nope + rope
    against values of ``v_head_dim``, through the flash kernels."""

    cfg: DecoderConfig
    mesh: object = None

    PARAM_RULES = (
        (r"attn/q_a/kernel$", ("fsdp", None)),  # [d, q_rank]
        (r"attn/q_b/kernel$", (None, "tp", None)),  # [q_rank, H, nope + rope]
        (r"attn/kv_a/kernel$", ("fsdp", None)),  # [d, kv_rank + rope]
        (r"attn/kv_b/kernel$", (None, "tp", None)),  # [kv_rank, H, nope + v]
        (r"attn/o/kernel$", ("tp", None, "fsdp")),  # [H, v, d]
    )

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, labels=None):
        cfg, dt = self.cfg, self.cfg.compute_dtype
        heads, nope, rope, v_dim = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        scaling = dict(cfg.rope_scaling)
        with jax.named_scope("tos.mla"):
            # a recomputed layer keeps the two latents (REMAT_POLICY), q_rank + kv_rank + rope values a
            # token, and runs the up-projections again from them
            c_q = checkpoint_name(nn.Dense(cfg.q_lora_rank, use_bias=False, dtype=dt, name="q_a")(x), KEPT_PROJECTED)
            q = nn.DenseGeneral((heads, nope + rope), use_bias=False, dtype=dt, name="q_b")(
                _norm(cfg, "q_norm")(c_q))  # [B, L, H, 192]
            kv = checkpoint_name(
                nn.Dense(cfg.kv_lora_rank + rope, use_bias=False, dtype=dt, name="kv_a")(x), KEPT_PROJECTED)
            c_kv = _norm(cfg, "kv_norm")(kv[..., :cfg.kv_lora_rank])
            k_rope = kv[..., cfg.kv_lora_rank:]  # [B, L, rope]: one for all heads
            up = nn.DenseGeneral((heads, nope + v_dim), use_bias=False, dtype=dt, name="kv_b")(c_kv)
            k_nope, v = up[..., :nope], up[..., nope:]

            inv_freq = yarn_inv_freq(rope, cfg.rope_theta, scaling)
            factor = scaling.get("factor", 1.0)
            rope_scale = yarn_mscale(factor, scaling.get("mscale", 1.0)) / yarn_mscale(
                factor, scaling.get("mscale_all_dim", 0.0))
            q_rope = _rope_interleaved(q[..., nope:], positions, inv_freq, rope_scale)
            k_rope = _rope_interleaved(k_rope, positions, inv_freq, rope_scale)
            q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], k_nope.shape[:-1] + (rope,))], axis=-1)
            softmax_scale = (nope + rope) ** -0.5 * yarn_mscale(factor, scaling.get("mscale_all_dim", 0.0)) ** 2

            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # [B, H, L, ·]
            out = transformer._dispatch_attention(
                q, k, v, cfg.attention, self.mesh, segment_ids=segment_ids, scale=softmax_scale, **_rule(labels))
            out = out.transpose(0, 2, 1, 3)  # [B, L, H, v]
            return checkpoint_name(
                nn.DenseGeneral(cfg.hidden_size, axis=(-2, -1), use_bias=False, dtype=dt, name="o")(out), KEPT_ATTENDED)


def _rule(labels, window=None):
    """The attention's rule with what it takes: the second rule where the
    model was given labels, the third in a windowed layer, else the call as
    it was."""
    if window is not None:
        if labels is not None:
            raise ValueError("decoder: a windowed layer knows no block-diffusion objective")
        return {"rule": "window", "window": window}
    return {} if labels is None else {"rule": "block_diffusion", "labels": labels}


class GroupedQueryAttention(nn.Module):
    """``layer.heads`` query heads over ``num_key_value_heads`` key/value
    heads of ``head_dim``: query head ``h`` reads key/value head ``h //
    (heads / kv heads)`` (the flash kernels read it in place). Under
    ``qk_norm`` every head of q and of k goes through an RMSNorm over its
    ``head_dim`` with one learned weight for all heads. Then rotary
    positions, halves rotated together: over the whole head at
    ``rope_theta``, or as the layer's ``rope`` says (its own base, YaRN's
    frequencies with cos and sin times ``attention_factor``, the first
    ``partial_rotary_factor`` of the head alone). Scores times ``head_dim **
    -0.5`` under the layer's rule: the whole document, or ``layer.window``
    positions of it. Under ``layer.gate`` head ``h``'s output is multiplied by
    ``sigmoid(x W_g)[h]``, ``x`` the sub-layer's normed input, before the
    output projection. ``cfg.attention_bias`` puts a bias on all four
    projections; without ``cfg.rotary`` q and k carry no positions at all.

    **Differential form** (``layer.lambda_init``; arXiv:2410.05258 as the
    decoder-hybrid-decoder family computes it). Query heads ``2i, 2i + 1``
    are pair ``i``; key heads ``2j, 2j + 1`` and ``V_j = [v_2j, v_2j+1]``
    (twice ``head_dim`` wide) are key/value pair ``j``, read by the query
    pairs ``i`` with ``i // (pairs / kv pairs) = j``. ``o_i = RMSNorm((A1 -
    lambda A2) V_j) (1 - lambda_init)``, ``A1 = softmax(q_2i k_2j^T)``, ``A2 =
    softmax(q_2i+1 k_2j+1^T)`` under the layer's rule, ``lambda = exp(lq1 .
    lk1) - exp(lq2 . lk2) + lambda_init`` from four learned vectors of
    ``head_dim``, the norm with a learned weight over the pair's ``2 *
    head_dim`` (eps 1e-5); lambda, the subtraction and the norm in float32
    (scope ``tos.diff_attn``). **Two maps a pair through the flash kernels
    as they are**: the query heads are put in the order (key/value pair,
    parity, pair within it), so that the heads reading key head ``2j + p``
    are neighbours, a group of the kernels' (nothing repeated on the key
    side); every key head is given the pair's whole ``V_j``, keys of
    ``head_dim`` under values of twice that (``V_j`` is in HBM twice, once a
    parity); ``A1 V_j`` and ``A2 V_j`` come back as two heads' outputs and
    are subtracted outside the kernel.

    ``layer.hands_on``: the call returns ``(y, {"k": k, "v": v})``, its keys
    and values as the projections return them ``[B, L, kv heads, head_dim]``
    (what a recomputed layer keeps anyway). ``shared`` (such a dict, handed on
    by an earlier layer): the layer is a **cross attention** — it has a query
    projection and no key or value projection of its own, reads ``shared``'s,
    and runs under ``tos.cross_attn``."""

    cfg: DecoderConfig
    mesh: object
    layer: HeadsPlan

    PARAM_RULES = (
        (r"attn/(q|k|v)/kernel$", ("fsdp", "tp", None)),  # [d, heads, head_dim]
        (r"attn/gate/kernel$", ("fsdp", "tp")),  # [d, heads]
        (r"attn/o/kernel$", ("tp", None, "fsdp")),  # [H, head_dim, d]
    )

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, labels=None, shared=None):
        cfg, dt, layer = self.cfg, self.cfg.compute_dtype, self.layer
        heads, width = layer.heads, cfg.head_dim or cfg.hidden_size // cfg.num_attention_heads
        kv_heads = cfg.kv_heads or heads
        scope = "tos.cross_attn" if shared is not None else "tos.gqa" if layer.window is None else "tos.swa"
        with jax.named_scope(scope):
            dense = lambda n, name: nn.DenseGeneral(  # noqa: E731
                (n, width), use_bias=cfg.attention_bias, dtype=dt, name=name)
            # named before any head norm, whose backward reads them: what a recomputed layer keeps (REMAT_POLICY)
            q = checkpoint_name(dense(heads, "q")(x), KEPT_PROJECTED)  # [B, L, heads, width]
            k, v = (shared["k"], shared["v"]) if shared is not None else (
                checkpoint_name(dense(kv_heads, name)(x), KEPT_PROJECTED) for name in ("k", "v"))
            made = {"k": k, "v": v}
            norm = (lambda t, name: _norm(cfg, name)(t)) if cfg.qk_norm else (lambda t, name: t)
            rope = _rotary(cfg, dict(layer.rope), width) if cfg.rotary else (lambda t, positions: t)
            q = rope(norm(q, "q_norm"), positions)
            k = rope(norm(k, "k_norm"), positions)
            attend = functools.partial(
                transformer._dispatch_attention, impl=cfg.attention, mesh=self.mesh, segment_ids=segment_ids,
                **_rule(labels, layer.window))
            if layer.lambda_init is None:
                q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # [B, ·, L, width]
                out = attend(q, k, v).transpose(0, 2, 1, 3)  # [B, L, H, width]
            else:
                out = self._differential(attend, q, k, v)  # [B, L, H / 2, 2 width]
            if layer.gate:
                with jax.named_scope("tos.attn_gate"):
                    gate = nn.Dense(heads, use_bias=False, dtype=dt, name="gate")(x)  # [B, L, H]
                    out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dt)[..., None]
            y = checkpoint_name(
                nn.DenseGeneral(cfg.hidden_size, axis=(-2, -1), use_bias=cfg.attention_bias, dtype=dt, name="o")(out),
                KEPT_ATTENDED)
            return (y, made) if layer.hands_on else y

    def _differential(self, attend, q, k, v):
        """The differential form (the class's text) of ``q`` ``[B, L, H,
        width]`` on ``k``, ``v`` ``[B, L, K, width]``: ``[B, L, H / 2, 2
        width]``, the pairs in the published order."""
        dt, lambda_init = self.cfg.compute_dtype, self.layer.lambda_init
        batch, length, heads, width = q.shape
        kv_pairs, per = k.shape[2] // 2, heads // k.shape[2]  # query pairs a key/value pair
        # query head 2 (j per + r) + p -> (j, p, r): the readers of key head 2j + p side by side
        q = q.reshape(batch, length, kv_pairs, per, 2, width).transpose(0, 2, 4, 3, 1, 5).reshape(
            batch, heads, length, width)
        k = k.transpose(0, 2, 1, 3)
        v = v.reshape(batch, length, kv_pairs, 2 * width).transpose(0, 2, 1, 3)  # V_j [B, J, L, 2 width]
        v = jnp.broadcast_to(v[:, :, None], (batch, kv_pairs, 2, length, 2 * width)).reshape(
            batch, 2 * kv_pairs, length, 2 * width)
        out = attend(q, k, v).reshape(batch, kv_pairs, 2, per, length, 2 * width)
        with jax.named_scope("tos.diff_attn"):
            vectors = [self.param("lambda_" + name, nn.initializers.normal(0.1), (width,), jnp.float32)
                       for name in ("q1", "k1", "q2", "k2")]
            lam = (jnp.exp(jnp.sum(vectors[0] * vectors[1])) - jnp.exp(jnp.sum(vectors[2] * vectors[3]))
                   + lambda_init)
            diff = out[:, :, 0].astype(jnp.float32) - lam * out[:, :, 1].astype(jnp.float32)  # [B, J, per, L, 2w]
            diff = nn.RMSNorm(epsilon=1e-5, dtype=jnp.float32, name="subln")(diff) * (1.0 - lambda_init)
            return diff.astype(dt).reshape(batch, heads // 2, length, 2 * width).transpose(0, 2, 1, 3)


def _scan_rows(dt, x, b, c, *rest, interpret):
    """:func:`~tensorflowonspark_tpu.ops.selective_scan.selective_scan` in
    :func:`_per_shard`'s order: what is split over the rows (the segment
    ids, if any, as ``[B, L, 1]``), then ``a`` and ``skip``, whole."""
    *ids, a, skip = rest
    return selective_scan.selective_scan(dt, x, b, c, a, skip, ids[0][..., 0] if ids else None, interpret=interpret)


def causal_conv(xs, kernel, bias, segment_ids=None):
    """``silu(bias + sum_j kernel[j] * xs_{t-j})`` a channel, ``j`` under
    ``len(kernel)``: the state-space layer's short convolution on ``xs``
    ``[B, L, D]`` (``kernel`` ``[taps, D]``, row ``j`` the tap ``j``
    positions back). A term from before the row's start or from another
    document of a packed row (``segment_ids``) is zero. Float32 inside."""
    length = xs.shape[1]
    wide = xs.astype(jnp.float32)
    total = bias.astype(jnp.float32) + kernel[0] * wide
    for back in range(1, kernel.shape[0]):
        term = jnp.pad(wide, ((0, 0), (back, 0), (0, 0)))[:, :length]
        if segment_ids is not None:
            same = jnp.pad(segment_ids, ((0, 0), (back, 0)), constant_values=-1)[:, :length] == segment_ids
            term = jnp.where(same[..., None], term, 0.0)
        total = total + kernel[back] * term
    return nn.silu(total).astype(xs.dtype)


def _dt_bias_init(key, shape, dtype=jnp.float32, low=1e-3, high=0.1, floor=None):
    """The family's: the inverse softplus of a step drawn log-uniform in
    [``low``, ``high``], floored at ``floor`` if any."""
    step = jnp.exp(jax.random.uniform(key, shape, dtype) * (math.log(high) - math.log(low)) + math.log(low))
    if floor is not None:
        step = jnp.maximum(step, floor)
    return step + jnp.log(-jnp.expm1(-step))


class MambaMixer(nn.Module):
    """A selective state-space layer (Mamba, arXiv:2312.00752) as the
    decoder-hybrid-decoder family holds it, ``D = MAMBA_EXPAND * hidden``
    channels, ``N = MAMBA_STATES`` states, ``R = dt_rank``: ``[xs, z] = u
    W_in``; ``c = causal_conv(xs)`` (:func:`causal_conv`, scope
    ``tos.ssm_conv``); ``[r, B, C] = c W_x``; ``dt = r W_dt + b_dt``; ``A =
    -exp(A_log)``; ``y`` the selective scan of
    :mod:`~tensorflowonspark_tpu.ops.selective_scan` (``Delta =
    softplus(dt)``, float32 state, the skip ``D * c`` included, restarting
    with the convolution at every document of a packed row; scope
    ``tos.ssm_scan``); result ``(y * silu(z)) W_out``. Returns ``(result,
    made)``: ``made`` is ``{"memory": y}`` (before the gate) where later
    layers read it (``layer.hands_on``), else empty. A recomputed layer keeps
    ``y``, the scan's boundary states and the result
    (:data:`REMAT_POLICY`); the products before the scan run again."""

    cfg: DecoderConfig
    mesh: object = None
    layer: HeadsPlan = None

    PARAM_RULES = (
        (r"mamba/in_proj/kernel$", ("fsdp", "tp")),  # [d, 2 D]
        (r"mamba/conv_kernel$", (None, "tp")),  # [taps, D]
        (r"mamba/(conv_bias|skip)$", ("tp",)),  # [D]
        (r"mamba/x_proj/kernel$", ("tp", None)),  # [D, R + 2 N]
        (r"mamba/dt_proj/kernel$", (None, "tp")),  # [R, D]
        (r"mamba/dt_proj/bias$", ("tp",)),
        (r"mamba/a_log$", ("tp", None)),  # [D, N]
        (r"mamba/out_proj/kernel$", ("tp", "fsdp")),  # [D, d]
    )

    @nn.compact
    def __call__(self, x, segment_ids=None):
        cfg, dt = self.cfg, self.cfg.compute_dtype
        inner, states, rank = cfg.d_inner, MAMBA_STATES, cfg.dt_rank
        with jax.named_scope("tos.mamba"):
            xz = nn.Dense(2 * inner, use_bias=False, dtype=dt, name="in_proj")(x)
            xs, z = xz[..., :inner], xz[..., inner:]
            with jax.named_scope("tos.ssm_conv"):
                c = causal_conv(
                    xs, self.param("conv_kernel", nn.initializers.normal(MAMBA_TAPS ** -0.5),
                                   (MAMBA_TAPS, inner), jnp.float32),
                    self.param("conv_bias", nn.initializers.zeros, (inner,), jnp.float32), segment_ids)
            low = nn.Dense(rank + 2 * states, use_bias=False, dtype=dt, name="x_proj")(c)
            step = nn.Dense(inner, dtype=dt, bias_init=_dt_bias_init, name="dt_proj")(low[..., :rank])
            a_log = self.param(
                "a_log", lambda key, shape, dtype: jnp.broadcast_to(
                    jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape), (inner, states), jnp.float32)
            skip = self.param("skip", nn.initializers.ones, (inner,), jnp.float32)
            ids = () if segment_ids is None else (segment_ids[..., None],)
            y = _per_shard(
                _scan_rows, self.mesh, (step, c, low[..., rank:rank + states], low[..., rank + states:]) + ids,
                (-jnp.exp(a_log), skip))
            out = nn.Dense(cfg.hidden_size, use_bias=False, dtype=dt, name="out_proj")(y * nn.silu(z))
            return checkpoint_name(out, KEPT_ATTENDED), ({"memory": y} if self.layer.hands_on else {})


def _ssd_rows(xs, delta, b, c, *rest, heads, groups, chunk, interpret):
    """:func:`~tensorflowonspark_tpu.ops.ssd_scan.ssd_scan` in
    :func:`_per_shard`'s order and shapes: everything a position has as
    ``[B, L, width]`` (``xs`` the heads side by side, ``b`` and ``c`` the
    groups; the segment ids, if any, as ``[B, L, 1]``), then ``a`` and
    ``skip``, whole."""
    *ids, a, skip = rest
    by = lambda t, n: t.reshape(t.shape[:2] + (n, -1))  # noqa: E731
    y = ssd_scan.ssd_scan(by(xs, heads), delta, a, by(b, groups), by(c, groups), skip,
                          ids[0][..., 0] if ids else None, chunk=chunk, interpret=interpret)
    return y.reshape(xs.shape)


class Mamba2Mixer(nn.Module):
    """A Mamba-2 layer (arXiv:2405.21060) as the ``nemotron_h`` family holds
    it: ``H`` heads of ``P = mamba_head_dim`` channels in ``G`` groups, ``N =
    ssm_state_size`` states, **the heads and groups held here**
    (``cfg.heads_held``: whole groups). ``[z | xBC | dt] = u W_in`` (widths ``H
    P``, ``H P + 2 G N``, ``H``; no bias); ``xBC = causal_conv(xBC)``
    (:func:`causal_conv`, a bias, scope ``tos.ssm_conv``), split into ``x [H,
    P]``, ``B`` and ``C [G, N]``; ``Delta = softplus(dt + b_dt)`` a head,
    float32; ``a = -exp(A_log)`` one scalar a head; ``y`` the scan of
    :mod:`~tensorflowonspark_tpu.ops.ssd_scan` (a head reads its group's ``B``
    and ``C``; float32 decays, state and chunk sums; the skip ``D x``
    included; restarting with the convolution at every document of a packed
    row; scope ``tos.ssd_scan``); then the gated norm, **gate first, the norm
    over each group's ``H P / G`` channels** with a learned weight, float32:
    ``RMSNorm_group(y * silu(z))``; result ``y W_out``, **the held heads' part
    of that sum** (nothing stands in for the other shares'). A recomputed
    layer keeps the scan's ``y``, its chunk states and the result
    (:data:`REMAT_POLICY`); the products before the scan run again."""

    cfg: DecoderConfig
    mesh: object = None

    PARAM_RULES = (
        # columns [z | x | B | C | dt], rows the held heads': whole over ``tp`` (the scan under it waits: ROADMAP M4)
        (r"mamba2/in_proj/kernel$", ("fsdp", None)),  # [d, 2 H P + 2 G N + H]
        (r"mamba2/out_proj/kernel$", (None, "fsdp")),  # [H P, d]
    )

    @nn.compact
    def __call__(self, x, segment_ids=None):
        cfg, dt = self.cfg, self.cfg.compute_dtype
        heads, groups = cfg.share_of(cfg.mamba_num_heads, "Mamba-2 heads"), cfg.share_of(cfg.n_groups, "groups")
        inner, states = heads * cfg.mamba_head_dim, groups * cfg.ssm_state_size
        first = cfg.share[0] * heads  # the first held head's place in the model
        with jax.named_scope("tos.mamba2"):
            zxbcdt = nn.Dense(2 * inner + 2 * states + heads, use_bias=False, dtype=dt, name="in_proj")(x)
            z, xbc, step = zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * states], zxbcdt[..., -heads:]
            with jax.named_scope("tos.ssm_conv"):
                xbc = causal_conv(
                    xbc, self.param("conv_kernel", nn.initializers.normal(cfg.conv_kernel ** -0.5),
                                    (cfg.conv_kernel, inner + 2 * states), jnp.float32),
                    self.param("conv_bias", nn.initializers.zeros, (inner + 2 * states,), jnp.float32), segment_ids)
            dt_bias = self.param("dt_bias", functools.partial(
                _dt_bias_init, low=cfg.time_step_min, high=cfg.time_step_max, floor=cfg.time_step_floor),
                (heads,), jnp.float32)
            # the family's: A = 1 .. H over the model's heads, in their order
            a_log = self.param(
                "a_log", lambda key, shape, dtype: jnp.log(jnp.arange(first + 1, first + 1 + shape[0], dtype=dtype)),
                (heads,), jnp.float32)
            skip = self.param("skip", nn.initializers.ones, (heads,), jnp.float32)
            delta = jax.nn.softplus(step.astype(jnp.float32) + dt_bias)
            ids = () if segment_ids is None else (segment_ids[..., None],)
            y = _per_shard(
                functools.partial(_ssd_rows, heads=heads, groups=groups, chunk=cfg.chunk_size), self.mesh,
                (xbc[..., :inner], delta, xbc[..., inner:inner + states], xbc[..., inner + states:]) + ids,
                (-jnp.exp(a_log), skip))
            gated = (y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))).reshape(y.shape[:2] + (groups, -1))
            gated = gated * jax.lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + cfg.rms_norm_eps)
            gated = gated.reshape(y.shape) * self.param("norm_scale", nn.initializers.ones, (inner,), jnp.float32)
            out = nn.Dense(cfg.hidden_size, use_bias=False, dtype=dt, name="out_proj")(gated.astype(dt))
            return checkpoint_name(out, KEPT_ATTENDED)


class GatedMemory(nn.Module):
    """The cross-decoder's gated memory unit: ``(silu(u W_1) * m) W_2``,
    ``m`` the scan output an earlier state-space layer handed on (``[B, L,
    MAMBA_EXPAND * hidden]``, before that layer's own gate)."""

    cfg: DecoderConfig

    PARAM_RULES = (
        (r"gmu/in_proj/kernel$", ("fsdp", "tp")),  # [d, D]
        (r"gmu/out_proj/kernel$", ("tp", "fsdp")),  # [D, d]
    )

    @nn.compact
    def __call__(self, x, memory):
        cfg, dt = self.cfg, self.cfg.compute_dtype
        with jax.named_scope("tos.gmu"):
            gate = nn.Dense(cfg.d_inner, use_bias=False, dtype=dt, name="in_proj")(x)
            return checkpoint_name(
                nn.Dense(cfg.hidden_size, use_bias=False, dtype=dt, name="out_proj")(nn.silu(gate) * memory),
                KEPT_ATTENDED)


class SwiGLU(nn.Module):
    """``down(silu(gate x) * up x)``: the dense MLP, and a shared expert;
    under ``act`` ``relu2`` two matrices and no gate, ``down(relu(up x) ** 2)``."""

    cfg: DecoderConfig
    width: int
    act: str = "silu"

    PARAM_RULES = (
        (r"(mlp|shared)/(gate|up)/kernel$", ("fsdp", "tp")),  # [d, width]
        (r"(mlp|shared)/down/kernel$", ("tp", "fsdp")),  # [width, d]
    )

    @nn.compact
    def __call__(self, x):
        dt = self.cfg.compute_dtype
        if self.act == "relu2":
            hidden = jnp.square(nn.relu(nn.Dense(self.width, use_bias=False, dtype=dt, name="up")(x)))
        else:
            gate = nn.Dense(self.width, use_bias=False, dtype=dt, name="gate")(x)
            up = nn.Dense(self.width, use_bias=False, dtype=dt, name="up")(x)
            hidden = nn.silu(gate) * up
        return nn.Dense(self.cfg.hidden_size, use_bias=False, dtype=dt, name="down")(hidden)


class RoutedExperts(nn.Module):
    """Top-k routing over all the model's experts, the experts held here
    computed over the slots routed to them, beside the shared experts, if
    any. Returns ``(y, counts)``.

    ``scoring_func: sigmoid``: scores ``s = sigmoid(x W_r)`` in float32;
    chosen: the top-k of ``s + b`` (``b`` the selection bias: it picks, it
    does not weigh, and no gradient reaches it). ``softmax``: ``s =
    softmax(x W_r)`` over all the experts, the k largest, and no bias (the
    layer has no such parameter); either kind may stand beside a shared
    expert (``cfg.shared_width``). Where ``padding_slots`` is off, a padding
    position (segment id 0) chooses no expert: its routed term is zero, and
    only the shared expert sees it. Weights: ``s`` at the chosen (read by
    :func:`_scores_at`, a masked sum and not a gather), over their sum, times
    ``routed_scaling_factor``. Every slot whose expert is held here is
    computed — no capacity, nothing dropped; a slot whose expert lives on
    another chip adds nothing here (nor is anything put in its place).

    The slots are sorted by held expert and the experts run over the head of
    that order (:func:`_experts_on_rows`). A chip that holds all the experts
    runs it on all ``T * k`` rows. One that holds a share runs it on
    ``gm.compact_rows`` of them, twice the even share, **when the device's own
    count of the held slots says they fit, and on all ``T * k`` slots in any
    step when they do not** (``gm.either``: a ``lax.cond``, both branches
    compiled; the fallback is this same function on a share of the tokens at
    a time, all their slots, and gives what one pass over ``T * k`` rows
    gives): that fallback, not a bound on the routing, is what "nothing
    dropped" rests on. ``counts`` then also says which of the two ran
    (``layers_compact`` / ``layers_at_bound``, one of them 1) and what the way
    back to token order read of the compact buffer (``combine_rows_fetched``).
    ``mesh``: the step's devices, for that kernel alone (on more than one chip
    a Mosaic call runs under a ``shard_map``: ``gm._sum_over_slots``)."""

    cfg: DecoderConfig
    mesh: object = None

    PARAM_RULES = (
        (r"moe/router$", (None, None)),  # [d, E]: whole on every chip
        (r"moe/experts_(gate|up)$", ("ep", "fsdp", "tp")),  # [held, d, width]
        (r"moe/experts_down$", ("ep", "tp", "fsdp")),  # [held, width, d]
        (r"moe/latent_down/kernel$", ("fsdp", None)),  # [d, latent]
        (r"moe/latent_up/kernel$", (None, "fsdp")),  # [latent, d]
    ) + SwiGLU.PARAM_RULES

    @nn.compact
    def __call__(self, x, segment_ids=None):
        cfg = self.cfg
        batch, length, d = x.shape
        tokens, k = batch * length, cfg.num_experts_per_tok
        first, held = cfg.held
        width = cfg.moe_intermediate_size
        flat = x.reshape(tokens, d)
        gated = cfg.mlp_hidden_act != "relu2"

        with jax.named_scope("tos.moe_route"):
            router = self.param("router", _kernel_init(), (d, cfg.n_routed_experts), jnp.float32)
            logits = jnp.dot(flat.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST)  # [T, E]
            if cfg.scoring_func == "softmax":
                scores = jax.nn.softmax(logits, axis=-1)
                _, chosen = jax.lax.top_k(scores, k)  # [T, k]
            else:
                bias = self.param("router_bias", nn.initializers.normal(0.02), (cfg.n_routed_experts,), jnp.float32)
                scores = jax.nn.sigmoid(logits)
                _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
            picked = _scores_at(scores, chosen)
            weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor
            if not cfg.padding_slots and segment_ids is not None:
                # an expert past the router's last: held nowhere
                chosen = jnp.where(segment_ids.reshape(tokens, 1) > 0, chosen, cfg.n_routed_experts)
            order, group_sizes, local = gm.sort_slots(chosen.reshape(-1), first, held)
            rows_used = jnp.sum(group_sizes)

        routed_in, d_in = flat, d
        if cfg.moe_latent_size:
            d_in = cfg.moe_latent_size
            with jax.named_scope("tos.moe_latent"):
                routed_in = nn.Dense(d_in, use_bias=False, dtype=cfg.compute_dtype, name="latent_down")(flat)
        init = _kernel_init(batch_axis=(0,))
        gate = self.param("experts_gate", init, (held, d_in, width), jnp.float32) if gated else None
        up = self.param("experts_up", init, (held, d_in, width), jnp.float32)
        down = self.param("experts_down", init, (held, width, d_in), jnp.float32)
        per_token, shared = (routed_in, weights), (gate, up, down)
        slots = tokens * k
        counts = {
            "slots_routed": jnp.float32(slots), "slots_held": rows_used.astype(jnp.float32),
            "load_max_over_mean": jnp.max(group_sizes) * held / jnp.maximum(rows_used, 1).astype(jnp.float32),
        }
        compact = gm.compact_rows(slots, held, cfg.n_routed_experts)
        on_rows = _experts_on_rows if self.mesh is None or self.mesh.size == 1 else _on_mesh(self.mesh)
        if compact == slots:
            routed = on_rows(slots, *per_token, *shared, order, group_sizes)
        else:
            fits = rows_used <= compact
            routed = gm.either(on_rows, compact, fits, per_token, shared, order, local, group_sizes)
            with jax.named_scope("tos.moe_route"):  # the kernel's own list of steps, counted beside it
                fetched = moe_combine.rows_fetched(order[:compact] // k, group_sizes, tokens)
            counts.update(layers_compact=fits.astype(jnp.float32), layers_at_bound=1.0 - fits,
                          combine_rows_fetched=jnp.where(fits, fetched, 0.0))

        if cfg.moe_latent_size:
            with jax.named_scope("tos.moe_latent"):
                routed = nn.Dense(d, use_bias=False, dtype=cfg.compute_dtype, name="latent_up")(routed)
        with jax.named_scope("tos.moe_shared"):
            shared = SwiGLU(cfg, cfg.shared_width, cfg.mlp_hidden_act, name="shared")(flat) if cfg.shared_width else 0
        return (routed + shared).reshape(batch, length, d), counts


def _scores_at(scores, chosen):
    """``scores[t, chosen[t, j]]``, ``float32 [T, k]`` out of ``[T, E]``, as
    a masked sum over the experts: ``where``, not a product with a 0 / 1
    mask, and one term a ``(t, j)``, so exactly what ``take_along_axis``
    gives whatever the scores; its transpose is the same mask on the
    cotangent, one term a ``(t, e)`` since a token's experts are distinct. XLA
    makes one fusion of each (compare, select, reduce: ``[T, k, E]`` is never
    written), where a TPU walks a gather and the scatter-add that is its
    transpose index by index: 1.36 ms a call at ``sdar-30b-a3b.bd4-packed4k``'s
    ``T, k, E`` = 16,384, 8, 128 for a few hundred kilobytes, against 0.42 ms
    forward and 0.15 backward for the masked sum (a v5e; PERF.md §6, PR 43).
    ``lax.top_k``'s own values would not do: its differentiation rule gathers
    the tangent by the indices, and transposes to that scatter-add. The masked
    sum is ``T * k * E`` selects, so its cost grows with ``E`` where a
    gather's does not: right at the configurations' 64 to 256 experts; with
    experts in the thousands a gather would be the cheaper program again."""
    at_chosen = chosen[..., None] == jnp.arange(scores.shape[-1], dtype=chosen.dtype)
    return jnp.sum(jnp.where(at_chosen, scores[:, None, :], 0.0), axis=-1)


@functools.partial(jax.jit, static_argnums=0, static_argnames="mesh", inline=True)
def _experts_on_rows(rows, flat, weights, gate, up, down, order, group_sizes, mesh=None):
    """The held experts over the first ``rows`` of the sorted slots, weighed
    and summed into their tokens: ``[T, d]``. ``flat`` ``[T, d]``, ``weights``
    ``float32 [T, k]``, the experts' float32 matrices, and
    :func:`~tensorflowonspark_tpu.ops.grouped_matmul.sort_slots`' ``order``
    and group sizes. Right for any ``rows`` that holds every
    held slot (``sum(group_sizes) <= rows``); its scopes open in here, so
    that a ``cond`` round it carries none. ``jax.jit(inline=True)``: traced
    once for all the layers, branches and passes of a step that call it at
    one length, and that trace spliced in at each (traced anew at each, a
    warm start of ``sdar-30b-a3b`` took 8 s longer; a call that XLA inlines
    would hand its name to the grouped products' kernels: PERF.md §6, PR 35).
    ``mesh``: the devices of a step on more than one, for the kernel on the
    way back to token order (``gm._sum_over_slots``)."""
    dt, k = flat.dtype, weights.shape[1]
    with jax.named_scope("tos.moe_route"):
        sorted_in = gm.rows_to_slots(flat, order, group_sizes, rows, k, mesh)  # [rows, d]
        sorted_weights = weights.reshape(-1)[order[:rows]]
    with jax.named_scope("tos.moe_experts"):
        if gate is None:  # ``relu2``: two matrices an expert
            hidden = jnp.square(nn.relu(gm.grouped_matmul(sorted_in, up.astype(dt), group_sizes)))
        else:
            hidden = nn.silu(gm.grouped_matmul(sorted_in, gate.astype(dt), group_sizes)) * gm.grouped_matmul(
                sorted_in, up.astype(dt), group_sizes)
        sorted_out = gm.grouped_matmul(hidden, down.astype(dt), group_sizes)  # [rows, d]
    with jax.named_scope("tos.moe_route"):
        # weighted where it lies, then each token's slots among the rows summed
        weighted = (sorted_out.astype(jnp.float32) * sorted_weights[:, None]).astype(dt)
        return gm.slots_to_tokens(weighted, order, group_sizes, k, mesh)


@functools.lru_cache(maxsize=None)
def _on_mesh(mesh):
    """:func:`_experts_on_rows` on ``mesh``, one object a mesh: ``gm.either`` and its fallback are traced once a
    function they are handed."""
    return functools.partial(_experts_on_rows, mesh=mesh)


def _kernel_init(batch_axis=()):
    return nn.initializers.variance_scaling(1.0, "fan_in", "truncated_normal", batch_axis=batch_axis)


def sinkhorn(logits, iters, eps):
    """``exp(logits)`` ``[…, n, n]`` scaled ``iters`` times, rows to sum 1
    and then columns: a doubly stochastic matrix in the limit. One loop body
    (``lax.scan``), not ``iters`` copies of it: unrolled, the 40 small
    reductions of every sub-layer, forward, recomputed and backward, made
    the compiled step five times its size (PERF.md §6, PR 26)."""

    def round_(m, _):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + eps), None

    return jax.lax.scan(round_, jnp.exp(logits), None, length=iters)[0]


def _per_shard(fn, mesh, sharded, whole=()):
    """``fn(*sharded, *whole)`` of :mod:`~tensorflowonspark_tpu.ops.hyper_connection`
    or :mod:`~tensorflowonspark_tpu.ops.selective_scan`, its kernels
    interpreted anywhere but on a TPU (the flash kernels' convention). A
    Mosaic call has no partitioning rule (see :func:`transformer._flash`): on
    a mesh it runs per shard of the batch, ``sharded`` and every result (all
    ``[B, L, width]``) split over the data axes as :meth:`Decoder._constrain`
    leaves the streams, ``whole`` (small parameters) on every chip. Rows are
    independent of one another, so nothing is exchanged inside; the
    parameters' gradients are summed over the shards by ``shard_map``'s own
    transpose."""
    run = functools.partial(fn, interpret=jax.default_backend() != "tpu")
    if mesh is None or mesh.size == 1:
        return run(*sharded, *whole)
    from jax.sharding import PartitionSpec as P

    from tensorflowonspark_tpu.parallel.collectives import shard_map

    rows = P(transformer._batch_axes(mesh, sharded[0].shape[0]), None, None)
    # check_vma off: pallas_call outputs carry no varying-axes type
    return shard_map(
        run, mesh=mesh, in_specs=(rows,) * len(sharded) + (P(),) * len(whole), out_specs=rows,
        check_vma=False)(*sharded, *whole)


class HyperConnection(nn.Module):
    """The residual path of one sub-layer ``F`` over ``n = hc_mult`` streams
    ``X`` ``[B, L, n, d]``: ``h = sum_i H_pre,i X_i``, ``y = F(h)``,
    ``X'_i = sum_j H_res,ij X_j + H_post,i y``, the three maps computed from
    the streams themselves (``x~ = vec(X) / rms(vec(X))``):
    ``H_pre = sigmoid(a_pre x~ phi_pre + b_pre)``, ``H_post = 2 sigmoid(…)``,
    ``H_res = sinkhorn(clamp(a_res mat(x~ phi_res) + b_res))``. The maps'
    products take the streams as they are (``dtype``) and accumulate in
    float32; everything after them is float32.

    The streams are 235 MB at the benchmark's shape and everything else here
    is small, so the path is two passes over them forward and two backward
    (``ops/hyper_connection.py``): ``__call__`` is the first (``1 / rms``,
    ``z = x~ phi``, ``H_pre`` and ``h`` in one reading) and finishes the
    other two maps from ``z``; it returns ``(h, maps)``, and :meth:`merge`,
    the second pass, writes ``y`` back. The passes take ``vec(X)``, ``[B, L,
    n * d]``, and that is how :class:`Decoder` carries the streams (on a chip
    ``[B, L, n, d]`` is another tiling, and every reshape between the two a
    copy of the streams); given ``[B, L, n, d]``, the path answers in kind.
    ``maps`` carries the streams as the first pass handed them on: their two
    cotangents then meet inside its backward pass and not in an addition of
    XLA's."""

    cfg: DecoderConfig
    mesh: object = None

    PARAM_RULES = ()  # the maps are small: whole on every chip

    @nn.compact
    def __call__(self, streams):
        cfg = self.cfg
        streams = streams.reshape(streams.shape[:2] + (-1,))
        n, d = cfg.hc_mult, streams.shape[-1] // cfg.hc_mult
        with jax.named_scope("tos.mhc"):
            phi_init = nn.initializers.normal((n * d) ** -0.5)
            phi = jnp.concatenate([
                self.param("phi_pre", phi_init, (n, d, n), jnp.float32),
                self.param("phi_post", phi_init, (n, d, n), jnp.float32),
                self.param("phi_res", phi_init, (n, d, n * n), jnp.float32),
            ], axis=-1)
            alpha = [self.param("alpha_" + name, nn.initializers.constant(0.01), (), jnp.float32)
                     for name in ("pre", "post", "res")]
            b_pre = self.param("b_pre", nn.initializers.zeros, (n,), jnp.float32)
            b_post = self.param("b_post", nn.initializers.zeros, (n,), jnp.float32)
            b_res = self.param("b_res", lambda key, shape, dtype: 4.0 * jnp.eye(n, dtype=dtype), (n, n), jnp.float32)

            h, z, streams = _per_shard(hyper_connection.read, self.mesh, (streams,), (phi, alpha[0], b_pre))
            h_post = 2.0 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n] + b_post)
            h_res = sinkhorn(
                jnp.clip(alpha[2] * z[..., 2 * n:].reshape(z.shape[:-1] + (n, n)) + b_res,
                         cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max),
                cfg.hc_sinkhorn_iters, cfg.hc_eps)
            maps = jnp.concatenate([h_res.reshape(z.shape[:-1] + (n * n,)), h_post], axis=-1)
        return h, (streams, maps)

    @staticmethod
    def merge(streams, maps, y, mesh=None):
        shape, (streams, maps) = streams.shape, maps
        with jax.named_scope("tos.mhc"):
            return _per_shard(hyper_connection.merge, mesh, (streams, y, maps)).reshape(shape)


class AddResidual(nn.Module):
    """``x + F(x)`` on the one stream, ``[B, L, d]`` as :class:`Decoder`
    carries it or ``[B, L, 1, d]``."""

    cfg: DecoderConfig
    mesh: object = None

    PARAM_RULES = ()

    def __call__(self, streams):
        return streams.reshape(streams.shape[:2] + (-1,)), None

    @staticmethod
    def merge(streams, maps, y, mesh=None):
        return streams + y.reshape(streams.shape)


_RESIDUALS = {"add": AddResidual, "mhc": HyperConnection}
_MIXERS = {"mla": LatentAttention, "gqa": GroupedQueryAttention, "cross": GroupedQueryAttention,
           "mamba": MambaMixer, "gmu": GatedMemory, "mamba2": Mamba2Mixer}


class DecoderLayer(nn.Module):
    """One layer of the plan: a pre-norm mixer (an attention, a state-space
    layer, a gated memory unit), then a pre-norm feed-forward, each inside
    the layer's residual path; **a block of one sub-layer** where the plan
    names no mixer, or no feed-forward (``x + F(norm(x))``, ``F`` the one it
    names; its norm keeps the name it has in a whole layer, ``ln1`` or
    ``ln2``). ``carried``: what earlier layers handed on
    (``memory``; ``k`` and ``v``), read by the kinds ``gmu`` and ``cross``.
    Returns ``(streams, counts, made)``: ``counts`` what a routed
    feed-forward counted (else empty), ``made`` what this layer hands on to
    later ones (else empty): it leaves the layer as a result and enters its
    readers as an argument, so under ``nn.remat`` it is kept once and no
    reader computes it again."""

    cfg: DecoderConfig
    kinds: tuple
    mesh: object = None
    #: what the layer's mixer is where the layers differ
    heads: HeadsPlan = None

    @nn.compact
    def __call__(self, streams, positions, segment_ids=None, labels=None, carried=None):
        cfg = self.cfg
        mixer, feed_forward, residual = self.kinds
        path = _RESIDUALS[residual]

        counts, made = {}, {}
        if mixer is not None:
            h, maps = path(cfg, self.mesh, name="res_attn")(streams)
            u = _norm(cfg, "ln1")(h)
            if mixer == "mla":
                y = LatentAttention(cfg, self.mesh, name="attn")(u, positions, segment_ids, labels)
            elif mixer == "mamba":
                y, made = MambaMixer(cfg, self.mesh, self.heads, name="mamba")(u, segment_ids)
            elif mixer == "mamba2":
                y = Mamba2Mixer(cfg, self.mesh, name="mamba2")(u, segment_ids)
            elif mixer == "gmu":
                y = GatedMemory(cfg, name="gmu")(u, carried["memory"])
            else:
                y = GroupedQueryAttention(cfg, self.mesh, self.heads, name="attn")(
                    u, positions, segment_ids, labels, shared=carried if mixer == "cross" else None)
                if self.heads.hands_on:
                    y, made = y
            streams = path.merge(streams, maps, y, self.mesh)

        if feed_forward is not None:
            h, maps = path(cfg, self.mesh, name="res_mlp")(streams)
            h = _norm(cfg, "ln2")(h)
            if feed_forward == "moe":
                y, counts = RoutedExperts(cfg, self.mesh, name="moe")(h, segment_ids)
            else:
                with jax.named_scope("tos.dense_mlp"):
                    y = SwiGLU(cfg, cfg.intermediate_size, name="mlp")(h)
            streams = path.merge(streams, maps, y, self.mesh)
        return streams, counts, made


class Decoder(nn.Module):
    cfg: DecoderConfig
    mesh: object = None

    def _constrain(self, x):
        if self.mesh is None or self.mesh.size == 1:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P

        batch = transformer._batch_axes(self.mesh, x.shape[0])
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(batch, *([None] * (x.ndim - 1)))))

    @nn.compact
    def __call__(self, tokens, positions=None, segment_ids=None, labels=None, head_from=0):
        """``labels`` (``int32 [B, L]``) asks the attention for its second
        rule; ``head_from`` (static) is the first position whose logits are
        wanted (the final norm and the head run from there on)."""
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.compute_dtype, name="embed")
        x = embed(tokens)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None, :], tokens.shape)
        streams = self._constrain(jnp.tile(x, (1, 1, cfg.hc_mult)))  # vec(X): the embedding copied to every stream
        layer = nn.remat(DecoderLayer, static_argnums=(), policy=REMAT_POLICY) if cfg.remat else DecoderLayer
        counted, carried = [], {}
        for i, kinds in enumerate(cfg.plan):
            streams, counts, made = layer(cfg, kinds, self.mesh, cfg.heads_plan(i), name="layer_{}".format(i))(
                streams, positions, segment_ids, labels, carried)
            streams = self._constrain(streams)
            carried = dict(carried, **made)
            if counts:
                counted.append(counts)
        scans = sum(kinds[0] == "mamba2" for kinds in cfg.plan)
        if scans:
            obs.counter(
                "ssd_scan_chunks_total",
                help="chunks the Mamba-2 layers' scans walked (rows x chunks a row, summed over the layers): each one "
                "grid step a group of heads, forward and again backward")
            self.sow("counters", "ssd_scan_chunks", jnp.float32(
                scans * tokens.shape[0] * ssd_scan.chunks_of(tokens.shape[1], cfg.chunk_size)[1]))
        if carried:
            obs.counter(
                "ssm_state_carried_bytes_total",
                help="bytes that crossed layers beside the residual stream: a state-space layer's scan output and a "
                "full layer's keys and values, handed to the layers that read them")
            self.sow("counters", "ssm_state_carried_bytes",
                     jnp.float32(sum(t.size * t.dtype.itemsize for t in carried.values())))
        if counted:
            # registered here, by name and with their help; the step carries the
            # values out and TrainStep books them (obs.book_carried)
            obs.counter(
                "moe_slots_routed_total", help="token slots the routed-expert layers routed (tokens x experts per token)")
            obs.counter("moe_slots_held_total", help="routed slots whose expert this chip holds (and so computed)")
            obs.gauge(
                "moe_expert_load_max_over_mean",
                help="fullest held expert's slots over the held experts' mean, mean over the routed layers, "
                "last booked step")
            self.sow("counters", "moe_slots_routed", sum(c["slots_routed"] for c in counted))
            self.sow("counters", "moe_slots_held", sum(c["slots_held"] for c in counted))
            self.sow("gauges", "moe_expert_load_max_over_mean",
                     sum(c["load_max_over_mean"] for c in counted) / len(counted))
        if any("layers_compact" in c for c in counted):
            obs.counter(
                "moe_layers_compact_total",
                help="routed layers of a step whose held slots fitted the compact slot buffer (twice the even share)")
            obs.counter(
                "moe_layers_at_bound_total",
                help="routed layers of a step that fell back to the whole slot buffer (tokens x experts per token rows)")
            obs.counter(
                "moe_combine_rows_fetched_total",
                help="slot buffer rows that one call a routed layer of the way back to token order brought in (128-row "
                "windows visited); over moe_slots_held_total, the read's amplification")
            for name in ("layers_compact", "layers_at_bound", "combine_rows_fetched"):
                self.sow("counters", "moe_" + name, sum(c.get(name, 0.0) for c in counted))
        summed = sum(jnp.split(streams[:, head_from:].astype(jnp.float32), cfg.hc_mult, axis=-1))
        x = _norm(cfg, "ln_f")(summed.astype(cfg.compute_dtype))
        if cfg.tie_word_embeddings:
            logits = embed.attend(x)  # x E^T: the embedding's matrix is the head's
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.compute_dtype, name="lm_head")(x)
        return logits.astype(jnp.float32)


_SHARED_RULES = (
    (r"embed/embedding$", ("fsdp", None)),  # [vocab, d]
    (r"lm_head/kernel$", ("fsdp", "tp")),  # [d, vocab]
)


def param_rules(cfg):
    """The placement rules of the kinds ``cfg``'s plan uses."""
    rules = []
    for attention, feed_forward, residual in cfg.plan:
        modules = [] if attention is None else [_MIXERS[attention]]
        modules += [] if feed_forward is None else [RoutedExperts if feed_forward == "moe" else SwiGLU]
        for module in modules + [_RESIDUALS[residual]]:
            rules += [rule for rule in module.PARAM_RULES if rule not in rules]
    return tuple(rules) + _SHARED_RULES


def make_param_specs(model):
    """``param_spec_fn`` for ``SyncDataParallel``: the rules of this model's
    layer kinds through ``transformer.param_specs``' resolution (axes the
    mesh lacks, or that do not divide, are dropped)."""
    rules = param_rules(model.cfg)
    return lambda params, mesh, tp_axis="tp": transformer.param_specs(params, mesh, tp_axis=tp_axis, rules=rules)


@register("decoder")
def create_model(mesh=None, **cfg):
    return Decoder(DecoderConfig.from_dict(cfg), mesh=mesh)
