"""What the decoder's test files share: the reference's configuration at toy
widths that keep every ratio of the published model (queries and keys wider
than values, 8 routed experts top-2 of which 3 are held here, 4 residual
streams, one dense layer and two routed ones), packed batches as the text
plane emits them, and comparisons with a scale-aware tolerance."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.reference import moe_lm as reference  # noqa: E402,F401  (re-exported)
from tensorflowonspark_tpu.models import get_model, transformer  # noqa: E402

YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 32, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1}
#: the reference's configuration (the benchmark file's keys) at toy widths
REF = {
    "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 2, "q_lora_rank": 12, "kv_lora_rank": 8, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000, "rope_scaling": YARN,
    "intermediate_size": 80, "moe_intermediate_size": 16, "router_experts": 8, "experts_held": [2, 3],
    "num_experts_per_tok": 2, "n_shared_experts": 1, "routed_scaling_factor": 2,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rms_norm_eps": 1e-6,
}
SEQ = 48


def program_config(ref, **over):
    cfg = {k: v for k, v in ref.items() if k != "router_experts"}
    cfg["n_routed_experts"] = ref["router_experts"]
    cfg.update(attention="plain", dtype="float32")
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def params():
    return reference.init_params(jax.random.PRNGKey(7), REF)


def packed_batch(rows=2, seq=SEQ, seed=0, vocab=REF["vocab_size"]):
    """Rows of packed documents with a padded tail, as the text plane emits
    them: segment ids 1, 2, … then 0; positions restart in every document."""
    rng = np.random.default_rng(seed)
    tokens = rng.integers(1, vocab, (rows, seq + 1))
    seg, pos = np.zeros_like(tokens), np.zeros_like(tokens)
    for r in range(rows):
        cuts = sorted(rng.choice(np.arange(4, seq - 6), 2, replace=False)) + [seq - 3]
        start = 0
        for i, cut in enumerate(cuts):
            seg[r, start:cut], pos[r, start:cut] = i + 1, np.arange(cut - start)
            start = cut
        tokens[r, start:] = 0
    return {k: jnp.asarray(v, jnp.int32) for k, v in (("tokens", tokens), ("segment_ids", seg), ("positions", pos))}


def close(got, want, tol=2e-4):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * max(np.abs(want).max(), 1e-6))


def tree_close(got, want, tol=2e-4, skip=()):
    flat_got, flat_want = jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        if "/".join(str(getattr(p, "key", p)) for p in path) in skip:
            continue
        try:
            close(g, w, tol)
        except AssertionError as e:
            raise AssertionError("leaf {}: {}".format(jax.tree_util.keystr(path), e)) from None


def hidden(seed, rows=2, seq=SEQ, d=REF["hidden_size"]):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, seq, d), jnp.float32)


def program_loss(ref=REF, **over):
    model = get_model("decoder", **program_config(ref, **over))
    return model, transformer.make_loss_fn(model)
