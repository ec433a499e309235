"""Operations and bytes the ``moe_lm`` family's step needs, from shapes and
from what the routing sent to the experts held here.

The conventions are ``flops.py``'s: two operations per multiply-add, the
forward pass once and the backward pass twice that, nothing for
recomputation; attention causal *within each packed segment*. A routed
expert's work is counted by the slots that reached it (the program's
``moe_slots_held_total``), not by the bound on them; experts held on other
chips are not this chip's work. Elementwise work (norms, rotary, SiLU, the
hyper-connections' stream mixing, Sinkhorn) is not counted.
"""


def macs_per_token(cfg):
    """Forward multiply-adds per token of every matrix product but the routed
    experts', by part: per layer of its kind, and the head."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v_dim = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q_rank, kv_rank, n = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["hc_mult"]
    return {
        "attention_proj": d * q_rank + q_rank * heads * (nope + rope) + d * (kv_rank + rope)
        + kv_rank * heads * (nope + v_dim) + heads * v_dim * d,
        "hyper_maps": 2 * n * d * (2 * n + n * n),  # two sub-layers: phi_pre, phi_post, phi_res
        "dense_mlp": 3 * d * cfg["intermediate_size"],
        "shared_expert": 3 * d * cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        "router": d * cfg["router_experts"],
        "head": d * cfg["vocab_size"],
    }


def expert_macs_per_slot(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layers(cfg):
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def matmul_flops(cfg, tokens, slots_held):
    """Forward + backward operations of a step's matrix products: ``tokens``
    through every layer and the head, ``slots_held`` routed slots (summed
    over the routed layers) through an expert each."""
    m = macs_per_token(cfg)
    dense, routed = layers(cfg)
    per_token = (
        (dense + routed) * (m["attention_proj"] + m["hyper_maps"]) + dense * m["dense_mlp"]
        + routed * (m["shared_expert"] + m["router"]) + m["head"])
    return 3 * 2 * (per_token * tokens + expert_macs_per_slot(cfg) * slots_held)


def attention_flops(cfg, pairs):
    """Forward + backward operations of attention over ``pairs`` query-key
    pairs per layer: scores and values forward, four products backward, each
    pair and head at the key width (nope + rope) or the value width."""
    per_pair = 2 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]) * cfg["num_attention_heads"]
    return cfg["num_hidden_layers"] * 3 * per_pair * pairs


def flash_bytes(cfg, rows, seq_len, itemsize=2):
    """Bytes the flash kernels of one step must move: q, k, dq, dk at the key
    width and v, o, do, dv at the value width, once each, in every layer."""
    width = 4 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) + 4 * cfg["v_head_dim"]
    return cfg["num_hidden_layers"] * rows * seq_len * cfg["num_attention_heads"] * width * itemsize


def expert_flops(cfg, slots_held):
    """Forward + backward operations of the grouped products over
    ``slots_held`` slots (all routed layers of a step)."""
    return 3 * 2 * expert_macs_per_slot(cfg) * slots_held


def expert_bytes(cfg, slots_held, itemsize=2):
    """Bytes the grouped products of one step must move: per routed layer the
    held experts' three matrices read forward and backward and their
    gradients written (3 x), per slot its input and output rows at the hidden
    width and its two hidden rows, forward and backward (2 x)."""
    _, routed = layers(cfg)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = routed * cfg["experts_held"][1] * 3 * d * f * 3
    per_slot = 2 * (2 * d + 2 * f)
    return (weights + per_slot * slots_held) * itemsize
