"""flops.py against counts made by hand."""

import numpy as np

from benchmarks import corpus, flops
from benchmarks.run import _load


def test_lm_block_by_hand():
    cfg = {"d_model": 1024, "d_ff": 4096, "vocab_size": 32000, "n_layers": 1, "n_heads": 16}
    # one block: q, k, v, o are 1024 x 1024 each, the MLP two 1024 x 4096; the head 1024 x 32000
    macs = 4 * 1024 * 1024 + 2 * 1024 * 4096 + 1024 * 32000
    assert flops.lm_matmul_flops_per_token(cfg) == 6 * macs
    full = _load("configs", "lm1024.json")
    assert abs(flops.lm_matmul_flops_per_token(full) / 1e9 - 2.1210) < 1e-3  # 24 blocks + the 1024 x 50304 head


def test_causal_pairs_and_attention():
    seg = np.array([[1, 1, 1, 2, 2, 0, 0, 0], [1, 1, 1, 1, 1, 1, 1, 1]])
    assert flops.causal_pairs(seg) == (6 + 3) + 36
    cfg = {"d_model": 1024, "n_heads": 16, "n_layers": 24}
    # per pair, head and product 2 * 64 operations; 16 heads; 6 products; 24 layers
    assert flops.lm_attention_flops(cfg, 10) == 24 * 6 * 2 * 64 * 16 * 10
    assert flops.flash_bytes(cfg, 4, 4096) == 24 * 8 * 4 * 4096 * 1024 * 2


def test_resnet_bottleneck_by_hand():
    cfg = _load("configs", "resnet50.json")
    shapes = flops.resnet_conv_shapes(cfg)
    assert len(shapes) == 53  # 1 stem + 16 blocks x 3 + 4 projections
    # the first bottleneck at 56 x 56: projection 64->256, 1x1 64->64, 3x3 64->64, 1x1 64->256
    assert shapes[1:5] == [(1, 1, 64, 256, 56, 56), (1, 1, 64, 64, 56, 56), (3, 3, 64, 64, 56, 56), (1, 1, 64, 256, 56, 56)]
    # the first stride-2 block computes its 1x1 at 56 x 56 and its 3x3 at 28 x 28 (v1.5)
    assert shapes[11:14] == [(1, 1, 256, 512, 28, 28), (1, 1, 256, 128, 56, 56), (3, 3, 128, 128, 28, 28)]
    gflops = flops.resnet_flops_per_image(cfg) / 1e9
    assert 24.0 < gflops < 25.2  # 3 x 2 x ~4.1 GMACs


def test_roofline_says_which_bound():
    peak = _load("peaks.json")["TPU v5 lite"]
    assert flops.roofline_seconds(197e12, 1.0, peak) == (1.0, "compute")
    assert flops.roofline_seconds(1.0, 819e9, peak) == (1.0, "memory")


def test_document_lengths_are_the_same_for_every_seed():
    law = _load("traffic", "packed4k.json")["corpus"]["doc_tokens"]
    lengths = corpus.doc_lengths(law, 2000)
    assert lengths.min() >= 16 and lengths.max() <= 4096
    assert 330 <= np.median(lengths) <= 370
