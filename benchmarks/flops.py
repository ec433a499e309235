"""Operations and bytes the algorithm needs, from shapes alone.

"Needed" is the convention of model FLOP/s utilization: two operations per
multiply-add, the forward pass once and the backward pass twice that, nothing
counted for recomputation. Attention is counted causal *within each packed
segment*: a kernel that computes the whole causal triangle of a packed row
does work the job does not need, and its utilization says so.
"""

import numpy as np


def lm_matmul_flops_per_token(cfg):
    """Forward + backward operations per token of the dense projections and
    the head (the embedding lookup is not a matrix product)."""
    d, ff, v = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    macs = cfg["n_layers"] * (4 * d * d + 2 * d * ff) + d * v
    return 3 * 2 * macs


def causal_pairs(segment_ids):
    """Query-key pairs attention needs in rows of packed segment ids
    (0 = padding, which needs none): a segment of n tokens has n (n + 1) / 2."""
    seg = np.asarray(segment_ids)
    pairs = 0
    for row in seg.reshape(-1, seg.shape[-1]):
        change = np.flatnonzero(np.diff(row)) + 1
        starts = np.concatenate([[0], change])
        lengths = np.diff(np.concatenate([starts, [len(row)]]))
        real = row[starts] > 0
        n = lengths[real].astype(np.int64)
        pairs += int((n * (n + 1) // 2).sum())
    return pairs


def lm_attention_flops(cfg, pairs):
    """Forward + backward operations of attention over ``pairs`` query-key
    pairs per layer: two products forward (scores, values) and four backward,
    each 2 * head_dim operations per pair and head."""
    per_pair = 2 * (cfg["d_model"] // cfg["n_heads"]) * cfg["n_heads"]
    return cfg["n_layers"] * 6 * per_pair * pairs


def flash_bytes(cfg, rows, seq_len, itemsize=2):
    """Bytes the attention kernels of one step must move per layer set:
    q, k, v, o, do, dq, dk, dv once each, ``[rows, heads, seq, head_dim]``."""
    return cfg["n_layers"] * 8 * rows * seq_len * cfg["d_model"] * itemsize


def resnet_conv_shapes(cfg):
    """(kh, kw, cin, cout, out_h, out_w) of every convolution, in order."""
    size = cfg["image_size"]
    shapes = [(7, 7, 3, 64, size // 2, size // 2)]
    hw, channels = size // 4, 64
    for stage, (n, filters) in enumerate(zip(cfg["stage_sizes"], cfg["filters"])):
        for i in range(n):
            stride = 2 if (i == 0 and stage > 0) else 1
            out = hw // stride
            if channels != filters * 4 or stride != 1:
                shapes.append((1, 1, channels, filters * 4, out, out))
            shapes.append((1, 1, channels, filters, hw, hw))
            shapes.append((3, 3, filters, filters, out, out))
            shapes.append((1, 1, filters, filters * 4, out, out))
            hw, channels = out, filters * 4
    return shapes


def resnet_flops_per_image(cfg):
    """Forward + backward operations per image: the convolutions and the head."""
    macs = sum(kh * kw * cin * cout * oh * ow for kh, kw, cin, cout, oh, ow in resnet_conv_shapes(cfg))
    macs += cfg["filters"][-1] * 4 * cfg["num_classes"]
    return 3 * 2 * macs


def roofline_seconds(flops, nbytes, peak):
    """Least time the chip could take and which bound sets it."""
    compute = flops / peak["bf16_flops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
