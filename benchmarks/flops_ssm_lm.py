"""Operations the ``ssm_lm`` family's step needs, from shapes and from the
consumed rows, whatever implements them.

The conventions are ``flops.py``'s: two operations per multiply-add, the
forward pass once and the backward pass twice that, nothing for
recomputation. Matrix products by the positions that go through them; a
differential attention layer (windowed, full or cross) by the pairs its rule
shows within each document (``flops_swa_lm.visible_pairs``: the window on a
windowed layer, the whole document on a full or cross layer), **two softmax
maps a query pair**: every one of the layer's query heads has a map of its
own, scores at ``head_dim`` and values at ``2 * head_dim`` (a pair's ``V_j``
is two heads wide). The selective scan, the short convolution, the norms,
SiLU, softmax, lambda and the sub-norm are elementwise work on the vector
unit and are not counted (``scan_bytes.py`` counts the scan's bytes): a
model FLOP/s utilization is a share of the matrix unit's peak.
"""

from benchmarks.flops_swa_lm import visible_pairs  # noqa: F401  (the family counts pairs with it)


def sizes(cfg):
    """``(D, S, R)``: a Mamba layer's channels, states and step rank."""
    d = cfg["hidden_size"]
    return 2 * d, 16, -(-d // 16)


def layer_kinds(cfg):
    """The kind of every layer held: ``mamba``, ``window``, ``full``,
    ``gmu`` or ``cross`` (the published model's rule, ``reference/ssm_lm.layer_kind``'s)."""
    half = (cfg.get("model_layers") or cfg["num_hidden_layers"]) // 2
    kinds = []
    for at in range(cfg.get("first_layer", 0), cfg.get("first_layer", 0) + cfg["num_hidden_layers"]):
        if at % cfg["mb_per_layer"] == 0:
            kinds.append("mamba" if at <= half else "gmu")
        elif at > half + 1:
            kinds.append("cross")
        else:
            kinds.append("window" if at < half and cfg.get("sliding_window") else "full")
    return kinds


def mixer_macs_per_token(cfg, kind):
    """Forward multiply-adds per token of one mixer's matrix products."""
    d, heads, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    width = d // heads
    inner, states, rank = sizes(cfg)
    if kind == "mamba":
        return d * 2 * inner + inner * (rank + 2 * states) + rank * inner + inner * d
    if kind == "gmu":
        return 2 * d * inner
    projected = heads * width + (0 if kind == "cross" else 2 * kv * width)
    return d * projected + heads * width * d  # q (k, v), and the output's [pairs * 2 width, d]


def macs_per_token(cfg):
    """Forward multiply-adds per token of every matrix product: the mixers',
    the feed-forwards' and the tied head's."""
    d = cfg["hidden_size"]
    return d * cfg["vocab_size"] + sum(
        mixer_macs_per_token(cfg, kind) + 3 * d * cfg["intermediate_size"] for kind in layer_kinds(cfg))


def matmul_flops(cfg, tokens):
    return 3 * 2 * macs_per_token(cfg) * tokens


def attention_flops(cfg, pairs_full, pairs_window, kinds=("window", "full", "cross")):
    """Forward + backward operations of the attention maps of the layers of
    ``kinds``: per visible pair and query head a score at ``head_dim`` and a
    value product at ``2 * head_dim`` forward, and twice that of each
    backward plus the scores' recomputation: the flash convention's six
    products, here 2 at ``head_dim`` + 1 at ``2 head_dim`` forward-like and
    the rest backward, in all ``3.5`` times the forward's."""
    heads, width = cfg["num_attention_heads"], cfg["hidden_size"] // cfg["num_attention_heads"]
    forward = 2 * (width + 2 * width)  # operations a pair and head: q.k over width, p.V over 2 width
    flops = 0
    for kind in layer_kinds(cfg):
        if kind in kinds and kind in ("window", "full", "cross"):
            flops += 3 * forward * heads * (pairs_window if kind == "window" else pairs_full)
    return flops


def flash_bytes(cfg, rows, seq_len, kinds, itemsize=2):
    """Bytes the attention kernels of one step must move in the layers of
    ``kinds``: per layer q and dq at the query heads x ``head_dim``, o and do
    at the query heads x ``2 head_dim``, k and dk at the key heads x
    ``head_dim``, and the pairs' values and their gradient, ``V_j`` at half
    the key heads x ``2 head_dim``, once each."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    width = cfg["hidden_size"] // heads
    per_position = sum(
        2 * heads * width + 2 * heads * 2 * width + 2 * kv * width + 2 * (kv // 2) * 2 * width
        for kind in layer_kinds(cfg) if kind in kinds)
    return rows * seq_len * per_position * itemsize
