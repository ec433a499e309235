"""Operations the ``ssd_lm`` family's step needs, from shapes, from the
consumed rows and from what the routing sent to the experts held here.

The conventions are ``flops.py``'s: two operations per multiply-add, the
forward pass once and the backward pass twice that, nothing for
recomputation. A block is one sub-layer (``hybrid_override_pattern``): a
Mamba-2 block's two projections at the heads and groups held here, the
attention block's at its held heads with its map by the pairs the causal rule
shows within each document (``flops_swa_lm.visible_pairs``), an expert block's
router, latent projections and shared expert by the positions and its routed
experts (two matrices in the latent) by the slots that reached the experts held
here (the program's ``moe_slots_held_total``). The scans' chunk products
(``ssd_ops.py`` counts them for the kernels' roofline), the convolution, the
norms, SiLU, relu squared and softmax are not counted: a sequential scan needs
none of the chunk form's products, and a model FLOP/s utilization is a share of
the matrix unit's peak that no implementation's choice should raise.
"""

from benchmarks.flops_swa_lm import visible_pairs  # noqa: F401  (the family counts pairs with it)


def block_kinds(cfg):
    """The pattern's letter of every block held (``M``, ``E`` or ``*``)."""
    first = cfg.get("first_layer", 0)
    return cfg["hybrid_override_pattern"][first:first + cfg["num_hidden_layers"]]


def mamba2_macs_per_token(cfg):
    d, heads, width = cfg["hidden_size"], cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    inner, states = heads * width, cfg["n_groups"] * cfg["ssm_state_size"]
    return d * (2 * inner + 2 * states + heads) + inner * d


def attention_macs_per_token(cfg):
    d, width = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * cfg["num_attention_heads"] * width + 2 * d * cfg["num_key_value_heads"] * width


def expert_block_macs_per_token(cfg):
    """Router, the two latent projections and the shared expert."""
    d = cfg["hidden_size"]
    return d * cfg["router_experts"] + 2 * d * cfg["moe_latent_size"] + 2 * d * cfg["moe_shared_expert_intermediate_size"]


def macs_per_token(cfg):
    """Forward multiply-adds per token of every matrix product but the routed
    experts', summed over the blocks, and the head's."""
    per = {"M": mamba2_macs_per_token(cfg), "*": attention_macs_per_token(cfg), "E": expert_block_macs_per_token(cfg)}
    return cfg["hidden_size"] * cfg["vocab_size"] + sum(per[kind] for kind in block_kinds(cfg))


def expert_macs_per_slot(cfg):
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def slots_per_step(cfg, rows, seq_len):
    """Slots the expert blocks route in a step (what the program's
    ``moe_slots_routed_total`` counts, padding included)."""
    return rows * seq_len * cfg["num_experts_per_tok"] * block_kinds(cfg).count("E")


def matmul_flops(cfg, tokens, slots_held):
    return 3 * 2 * (macs_per_token(cfg) * tokens + expert_macs_per_slot(cfg) * slots_held)


def attention_flops(cfg, pairs):
    """Forward + backward operations of the attention blocks' maps: scores and
    values forward, four products backward, each pair and held query head."""
    return 6 * 2 * cfg["head_dim"] * cfg["num_attention_heads"] * pairs * block_kinds(cfg).count("*")


def expert_flops(cfg, slots_held):
    return 3 * 2 * expert_macs_per_slot(cfg) * slots_held


def expert_bytes(cfg, slots_held, itemsize=2):
    """Bytes the grouped products of one step must move: per expert block the
    held experts' two matrices read forward and backward and their gradients
    written (3 x), per slot its input and output rows at the latent's width
    and its one hidden row, forward and backward (2 x)."""
    latent, wide = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    weights = block_kinds(cfg).count("E") * cfg["experts_held"][1] * 2 * latent * wide * 3
    return (weights + 2 * (2 * latent + wide) * slots_held) * itemsize
