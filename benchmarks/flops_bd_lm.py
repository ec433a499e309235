"""Operations and bytes the ``bd_lm`` family's step needs, from shapes, from
the consumed rows and from what the routing sent to the experts held here.

The conventions are ``flops.py``'s: two operations per multiply-add, the
forward pass once and the backward pass twice that, nothing for
recomputation. A step trains ``tokens`` data tokens and the model reads twice
as many positions, a clean copy and a noised one, so every product but the
head's is counted on both halves — **except in the last layer**, where of the
clean half only the key and value projections reach the loss (the noised half
attends them; the clean half's own queries, output projection, router and
experts feed nothing). Needed work leaves those out, whether the program
skips them or not, so a share of needed work does not rise when it does not.
Attention is counted by the pairs the block-diffusion mask shows
(:func:`visible_pairs`); a routed expert's work by the slots that reached it
(the program's ``moe_slots_held_total``, less the last layer's clean half's
share); experts held on other chips are not this chip's work. Elementwise
work (norms, rotary, SiLU, softmax) is not counted.
"""

import numpy as np


def macs_per_position(cfg):
    """Forward multiply-adds per position of the matrix products but the
    routed experts', by part."""
    d, heads, kv_heads, width = (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
                                 cfg["head_dim"])
    return {
        "q_and_o": 2 * d * heads * width,
        "k_and_v": 2 * d * kv_heads * width,
        "router": d * cfg["router_experts"],
        "head": d * cfg["vocab_size"],
    }


def expert_macs_per_slot(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def slots_per_step(cfg, rows, seq_len):
    """Slots the routed layers route in a step: both halves of every row, in
    every layer (what the program's ``moe_slots_routed_total`` counts)."""
    return rows * 2 * seq_len * cfg["num_experts_per_tok"] * cfg["num_hidden_layers"]


def needed_share_of_slots(cfg):
    """The share of a step's held slots that reaches the loss: all but the
    last layer's clean half's, the halves routing alike."""
    layers = cfg["num_hidden_layers"]
    return (2 * layers - 1) / (2 * layers)


def matmul_flops(cfg, tokens, slots_held):
    """Forward + backward operations of a step's matrix products on
    ``tokens`` data tokens (twice as many positions), ``slots_held`` routed
    slots (summed over the layers, as counted) through an expert each."""
    m, layers = macs_per_position(cfg), cfg["num_hidden_layers"]
    per_position = m["q_and_o"] + m["k_and_v"] + m["router"]
    macs = tokens * (
        (2 * layers - 1) * per_position  # both halves of every layer but the last, the last's noised half
        + m["k_and_v"]  # the last layer's clean half
        + m["head"])  # the noised half alone
    return 3 * 2 * (macs + expert_macs_per_slot(cfg) * slots_held * needed_share_of_slots(cfg))


def _blocks(segment_ids, positions, block_length):
    """``(n, squares)`` per real document of packed rows: its length and the
    sum of its diffusion blocks' squared sizes (whole blocks and a tail)."""
    seg, pos = np.asarray(segment_ids), np.asarray(positions)
    ends = (seg > 0) & (np.concatenate([seg[:, 1:], np.zeros_like(seg[:, :1])], axis=1) != seg)
    n = pos[ends].astype(np.int64) + 1
    whole, tail = n // block_length, n % block_length
    return n, whole * block_length ** 2 + tail ** 2


def visible_pairs(segment_ids, positions, block_length):
    """Query-key pairs the block-diffusion mask shows in the rows as the
    model reads them (both copies). A document of n tokens in blocks of sizes
    s_b has ``sum s_b^2`` pairs among its noised copy, ``(n^2 + sum s_b^2) /
    2`` from its clean queries and ``(n^2 - sum s_b^2) / 2`` from its noised
    queries to clean keys: ``n^2 + sum s_b^2`` in all."""
    n, squares = _blocks(segment_ids, positions, block_length)
    return int((n * n + squares).sum())


def attention_flops(cfg, pairs):
    """Forward + backward operations of attention over ``pairs`` visible
    pairs a layer (both halves' queries): scores and values forward, four
    products backward, each pair and query head at ``head_dim``. In the last
    layer the noised queries' pairs alone are needed: half of them (the
    clean and the noised queries of a document see equally many keys)."""
    per_pair = 2 * cfg["head_dim"] * cfg["num_attention_heads"]
    return (cfg["num_hidden_layers"] - 0.5) * 6 * per_pair * pairs


def flash_bytes(cfg, rows, seq_len, itemsize=2):
    """Bytes the attention of one step must move: per layer q, o, do, dq at
    the query heads and k, v, dk, dv at the key/value heads, once each, over
    the 2 x ``seq_len`` positions of every row (the last layer's clean half
    moves k, v, dk, dv alone)."""
    width, layers = cfg["head_dim"], cfg["num_hidden_layers"]
    queries = 4 * cfg["num_attention_heads"] * width * (2 * layers - 1)
    keys = 4 * cfg["num_key_value_heads"] * width * 2 * layers
    return rows * seq_len * (queries + keys) * itemsize


def expert_flops(cfg, slots_held):
    """Forward + backward operations of the grouped products over
    ``slots_held`` slots (all layers of a step, as the kernels ran them)."""
    return 3 * 2 * expert_macs_per_slot(cfg) * slots_held


def expert_bytes(cfg, slots_held, itemsize=2):
    """Bytes the grouped products of one step must move: per layer the held
    experts' three matrices read forward and backward and their gradients
    written (3 x), per slot its input and output rows at the hidden width and
    its two hidden rows, forward and backward (2 x)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["num_hidden_layers"] * cfg["experts_held"][1] * 3 * d * f * 3
    per_slot = 2 * (2 * d + 2 * f)
    return (weights + per_slot * slots_held) * itemsize
