"""Operations and bytes the ``swa_lm`` family's step needs, from shapes, from
the consumed rows and from what the routing sent to the experts held here.

The conventions are ``flops.py``'s: two operations per multiply-add, the
forward pass once and the backward pass twice that, nothing for
recomputation. Every layer has its own head count and its own rule: a full
layer's attention is counted by the pairs the causal rule shows within each
document, a sliding layer's by the pairs inside its window
(:func:`visible_pairs`), each at the layer's query heads. A routed expert's
work is counted by the slots that reached it (the program's
``moe_slots_held_total``); experts held on other chips are not this chip's
work. Elementwise work (norms, rotary, the gate's sigmoid, SiLU, softmax) is
not counted.
"""

import numpy as np


def layer_kinds(cfg):
    """``[(windowed, heads, dense)]`` of the layers the configuration keeps."""
    return [(cfg["layer_types"][i] == "sliding_attention", cfg["num_attention_heads_per_layer"][i],
             cfg["mlp_layer_types"][i] == "dense") for i in range(cfg["num_hidden_layers"])]


def macs_per_token(cfg):
    """Forward multiply-adds per token of every matrix product but the routed
    experts', summed over the layers, and the head's."""
    d, width, kv_heads = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    macs = d * cfg["vocab_size"]
    for _windowed, heads, dense in layer_kinds(cfg):
        macs += 2 * d * heads * width + 2 * d * kv_heads * width + d * heads  # q and o, k and v, the gate
        if dense:
            macs += 3 * d * cfg["intermediate_size"]
        else:
            macs += d * cfg["router_experts"] + 3 * d * cfg["shared_expert_intermediate_size"]
    return macs


def expert_macs_per_slot(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def routed_layers(cfg):
    return sum(not dense for _, _, dense in layer_kinds(cfg))


def slots_per_step(cfg, rows, seq_len):
    """Slots the routed layers route in a step (what the program's
    ``moe_slots_routed_total`` counts)."""
    return rows * seq_len * cfg["num_experts_per_tok"] * routed_layers(cfg)


def matmul_flops(cfg, tokens, slots_held):
    """Forward + backward operations of a step's matrix products: ``tokens``
    through every layer and the head, ``slots_held`` routed slots (summed
    over the routed layers) through an expert each."""
    return 3 * 2 * (macs_per_token(cfg) * tokens + expert_macs_per_slot(cfg) * slots_held)


def visible_pairs(segment_ids, window=None):
    """Query-key pairs the causal rule (with ``window``: the window rule)
    shows in packed rows (ids 1, 2, … then 0 for padding, which shows none):
    a document's ``p``-th token sees ``min(p, window)`` keys, itself among
    them."""
    pairs = 0
    seg = np.asarray(segment_ids)
    for row in seg.reshape(-1, seg.shape[-1]):
        starts = np.concatenate([[0], np.flatnonzero(np.diff(row)) + 1])
        lengths = np.diff(np.concatenate([starts, [len(row)]]))
        for n in lengths[row[starts] > 0].astype(np.int64):
            w = n if window is None else min(n, window)
            pairs += int(w * (w + 1) // 2 + (n - w) * w)
    return pairs


def attention_flops(cfg, pairs_full, pairs_window, windowed=None):
    """Forward + backward operations of attention: scores and values forward,
    four products backward, each pair and query head at ``head_dim``;
    ``pairs_full`` visible pairs a full layer, ``pairs_window`` a sliding one.
    ``windowed`` True or False counts the layers of that type alone."""
    flops = 0
    for is_windowed, heads, _dense in layer_kinds(cfg):
        if windowed is None or windowed == is_windowed:
            flops += 6 * 2 * cfg["head_dim"] * heads * (pairs_window if is_windowed else pairs_full)
    return flops


def flash_bytes(cfg, rows, seq_len, windowed, itemsize=2):
    """Bytes the attention kernels of one step must move in the layers of one
    type: per layer q, o, do, dq at the layer's query heads and k, v, dk, dv
    at the key/value heads, once each."""
    width, kv_heads = cfg["head_dim"], cfg["num_key_value_heads"]
    per_position = sum(4 * heads * width + 4 * kv_heads * width
                       for is_windowed, heads, _dense in layer_kinds(cfg) if is_windowed == windowed)
    return rows * seq_len * per_position * itemsize


def expert_flops(cfg, slots_held):
    """Forward + backward operations of the grouped products over
    ``slots_held`` slots (all routed layers of a step)."""
    return 3 * 2 * expert_macs_per_slot(cfg) * slots_held


def expert_bytes(cfg, slots_held, itemsize=2):
    """Bytes the grouped products of one step must move: per routed layer the
    held experts' three matrices read forward and backward and their
    gradients written (3 x), per slot its input and output rows at the hidden
    width and its two hidden rows, forward and backward (2 x)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = routed_layers(cfg) * cfg["experts_held"][1] * 3 * d * f * 3
    per_slot = 2 * (2 * d + 2 * f)
    return (weights + per_slot * slots_held) * itemsize
