"""The decoder's one-sub-layer dialect (``hybrid_override_pattern``: Mamba-2
blocks, attention blocks, latent mixture-of-experts blocks) at toy widths: the
plan and the parameter tree it builds, the model against the plain reference
(``benchmarks/reference/ssd_lm.py``: logits, loss and every leaf's gradient),
**the shares adding up** — the sub-layer results of every share of a block's
heads or experts, what every share computes alike counted once, against the
uncut reference's block — what a chip counts, and what ``from_dict`` refuses."""

import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu import obs
from tensorflowonspark_tpu.models import decoder, get_model, transformer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.reference import ssd_lm as reference  # noqa: E402

#: the published keys at toy numbers: 8 blocks ``M E M * E M E M``; 8 Mamba-2 heads of 16 in 2 groups of 16 states,
#: 4 query heads on 2 key/value heads of 16, 16 experts top-4 of two matrices in a latent of 32
PUBLISHED = dict(
    model_type="nemotron_h", hybrid_override_pattern="MEM*EMEM", num_hidden_layers=8, hidden_size=64, vocab_size=128,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, attention_bias=False,
    mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=16, expand=2,
    mamba_hidden_act="silu", mamba_proj_bias=False, use_conv_bias=True, use_bias=False, use_mamba_kernels=True,
    time_step_min=0.001, time_step_max=0.1, time_step_floor=0.0001,
    n_routed_experts=16, num_experts_per_tok=4, moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=96, moe_shared_expert_overlap=False, n_shared_experts=1, mlp_hidden_act="relu2",
    mlp_bias=False, routed_scaling_factor=5, n_group=1, topk_group=1, norm_topk_prob=True, intermediate_size=48,
    norm_eps=1e-5, layer_norm_epsilon=1e-5, rope_theta=10000, partial_rotary_factor=1, sliding_window=None,
    tie_word_embeddings=False, num_nextn_predict_layers=1, mtp_hybrid_override_pattern="*E", num_logits_to_keep=1,
    rescale_prenorm_residual=True, residual_in_fp32=False, max_position_embeddings=4096,
)
SEG = [[1] * 21 + [2] * 30 + [0] * 5, [1] * 56]


def _toy(**over):
    return dict(PUBLISHED, padding_slots=False, attention="flash_interpret", **over)


def _reference_cfg(cfg, shares=1, experts=None):
    """The reference's view of a share: the counts held, the router's width beside them."""
    held = experts or (0, cfg["n_routed_experts"])
    return dict(
        cfg, router_experts=cfg["n_routed_experts"], experts_held=list(held), heads_held=[0, shares],
        mamba_num_heads=cfg["mamba_num_heads"] // shares, n_groups=cfg["n_groups"] // shares,
        num_attention_heads=cfg["num_attention_heads"] // shares,
        num_key_value_heads=max(cfg["num_key_value_heads"] // shares, 1))


def _rows(seed=0):
    seg = np.asarray(SEG, np.int32)
    tokens = (np.random.default_rng(seed).integers(3, 128, seg.shape) * (seg > 0)).astype(np.int32)
    return jnp.asarray(tokens), jnp.asarray(seg)


def test_a_plan_of_one_sub_layer_blocks_builds():
    cfg = decoder.DecoderConfig.from_dict(_toy(first_layer=1, num_hidden_layers=5, model_layers=8))
    assert cfg.plan == ((None, "moe", "add"), ("mamba2", None, "add"), ("gqa", None, "add"), (None, "moe", "add"),
                        ("mamba2", None, "add"))
    assert not cfg.rotary and not cfg.qk_norm and cfg.rms_norm_eps == 1e-5 and cfg.shared_width == 96
    model = decoder.Decoder(cfg)
    tokens, seg = _rows()
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens, segment_ids=seg))["params"]
    assert set(params["layer_0"]) == {"ln2", "moe"} and set(params["layer_1"]) == {"ln1", "mamba2"}
    assert set(params["layer_2"]) == {"ln1", "attn"} and set(params["layer_2"]["attn"]) == {"q", "k", "v", "o"}
    moe, mamba = params["layer_0"]["moe"], params["layer_1"]["mamba2"]
    assert set(moe) == {"router", "router_bias", "latent_down", "experts_up", "experts_down", "latent_up", "shared"}
    assert set(moe["shared"]) == {"up", "down"}  # relu2: two matrices and no gate, the shared expert too
    assert moe["experts_up"].shape == (16, 32, 48) and moe["experts_down"].shape == (16, 48, 32)
    assert moe["latent_down"]["kernel"].shape == (64, 32) and moe["shared"]["up"]["kernel"].shape == (64, 96)
    assert mamba["in_proj"]["kernel"].shape == (64, 2 * 128 + 2 * 32 + 8) and mamba["conv_kernel"].shape == (4, 128 + 64)
    assert mamba["a_log"].shape == mamba["dt_bias"].shape == mamba["skip"].shape == (8,)
    assert set(params) == {"embed", "ln_f", "lm_head"} | {"layer_{}".format(i) for i in range(5)}
    rules = [pattern for pattern, _ in decoder.param_rules(cfg)]
    assert any("mamba2/in_proj" in rule for rule in rules) and any("latent_down" in rule for rule in rules)


def test_the_family_init_is_the_published_one():
    cfg = decoder.DecoderConfig.from_dict(_toy(heads_held=[1, 2]))
    tokens, seg = _rows()
    params = decoder.Decoder(cfg).init(jax.random.PRNGKey(0), tokens, segment_ids=seg)["params"]
    mamba = params["layer_0"]["mamba2"]
    # A = 1 .. 8 over the model's heads: the second share of two holds 5 .. 8
    np.testing.assert_allclose(jnp.exp(mamba["a_log"]), [5.0, 6.0, 7.0, 8.0], rtol=1e-6)
    step = jax.nn.softplus(mamba["dt_bias"])
    assert bool(jnp.all((step >= 0.001 * 0.999) & (step <= 0.1 * 1.001)))
    assert mamba["in_proj"]["kernel"].shape == (64, 2 * 64 + 2 * 16 + 4)  # 4 heads in 1 group


def test_model_matches_the_plain_reference():
    """Logits, loss and every leaf's gradient, float32, two packed rows with padding."""
    cfg = _toy()
    model = get_model("decoder", **cfg)
    ref_cfg = _reference_cfg(cfg)
    params = reference.init_params(jax.random.PRNGKey(5), ref_cfg)
    tokens, seg = _rows()
    real = np.asarray(seg) > 0
    with jax.default_matmul_precision("highest"):
        want = reference.logits_of(params, tokens, seg, ref_cfg)
        got, sown = model.apply({"params": params}, tokens, segment_ids=seg, mutable=["counters", "gauges"])
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real], rtol=2e-4, atol=2e-4)
    # three Mamba-2 blocks' scans of 2 rows x 4 chunks of 16; padding takes no routed slot
    assert float(sown["counters"]["ssd_scan_chunks"][0]) == 4 * 2 * 4
    assert float(sown["counters"]["moe_slots_held"][0]) == 3 * int(real.sum()) * 4
    batch = {"tokens": jnp.pad(tokens, ((0, 0), (0, 1))), "segment_ids": jnp.pad(seg, ((0, 0), (0, 1))),
             "positions": jnp.zeros((2, seg.shape[1] + 1), jnp.int32)}
    loss_fn = transformer.make_loss_fn(model)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        valid = reference.valid_targets({k: np.asarray(v) for k, v in batch.items()})
        want_loss, want_grads = jax.value_and_grad(lambda p: reference.loss_sum(p, batch, ref_cfg) / valid)(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    for (path, got_leaf), want_leaf in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree.leaves(want_grads)):
        name = "/".join(str(p.key) for p in path)
        apart = float(jnp.linalg.norm(got_leaf - want_leaf))
        assert apart <= 2e-4 * float(jnp.linalg.norm(want_leaf)) + 1e-9, name
    assert float(jnp.max(jnp.abs(grads["layer_1"]["moe"]["router_bias"]))) == 0.0  # it picks, it does not weigh


def _block(cfg, kind, seed=3):
    """The uncut block of ``kind``: its parameters, a normed input and the ids."""
    at = {"mamba2": 0, "moe": 1, "attn": 3}[kind]
    params = reference.init_params(jax.random.PRNGKey(seed), _reference_cfg(cfg))["layer_{}".format(at)]
    _, seg = _rows()
    u = jax.random.normal(jax.random.PRNGKey(seed + 1), seg.shape + (cfg["hidden_size"],), jnp.float32)
    return params[{"attn": "attn"}.get(kind, kind)], u, seg


@pytest.mark.parametrize("shares", [1, 2])
def test_mamba2_shares_add_up_to_the_uncut_block(shares):
    cfg = _toy()
    whole, u, seg = _block(cfg, "mamba2")
    with jax.default_matmul_precision("highest"):
        want = reference.mamba2(u, whole, seg, cfg)
        got = 0.0
        for index in range(shares):
            held = decoder.DecoderConfig.from_dict(dict(cfg, heads_held=[index, shares]))
            part = reference.share_params(whole, cfg, "mamba2", index, shares)
            got = got + decoder.Mamba2Mixer(held).apply({"params": part}, u, seg)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("shares", [1, 2, 4], ids=["whole", "a key/value head a share", "a key/value head held by two shares"])
def test_attention_shares_add_up_to_the_uncut_block(shares):
    cfg = _toy()
    whole, u, seg = _block(cfg, "attn")
    positions = jnp.zeros(seg.shape, jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = reference.attention(u, whole, seg)
        got = 0.0
        for index in range(shares):
            held = decoder.DecoderConfig.from_dict(dict(cfg, heads_held=[index, shares]))
            part = reference.share_params(whole, cfg, "attn", index, shares)
            assert part["q"]["kernel"].shape[1] == 4 // shares and part["k"]["kernel"].shape[1] == max(2 // shares, 1)
            got = got + decoder.GroupedQueryAttention(held, None, held.heads_plan(3)).apply(
                {"params": part}, u, positions, seg)
    real = np.asarray(seg) > 0  # a padding position attends to nothing in the reference, to itself in the kernels
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real], rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("shares", [1, 2, 4])
def test_expert_shares_add_up_to_the_uncut_block(shares):
    """The routed terms of every share of the experts, each through the latent
    and up again, with the shared expert counted once."""
    cfg = _toy()
    whole, u, seg = _block(cfg, "moe")
    ref_cfg = _reference_cfg(cfg)
    with jax.default_matmul_precision("highest"):
        want = reference.experts(u, whole, ref_cfg)
        shared = reference.experts(u, whole, ref_cfg, held=(0, 0))
        got, slots = -(shares - 1) * shared, 0.0
        for index in range(shares):
            count = 16 // shares
            held = decoder.DecoderConfig.from_dict(dict(cfg, experts_held=[index * count, count]))
            part = reference.share_params(whole, cfg, "moe", index, shares)
            y, counts = decoder.RoutedExperts(held).apply({"params": part}, u, seg)
            got, slots = got + y, slots + float(counts["slots_held"])
    real = np.asarray(seg) > 0  # padding takes no routed slot in the program
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real], rtol=2e-4, atol=2e-5)
    assert slots == int(real.sum()) * 4  # every real position's four slots, each held by one share


def test_whole_model_of_shares_is_the_references_share():
    """The model told its share against the reference given the same share: the
    partial sums go on to the next block as they are, on both sides."""
    cfg = _toy(heads_held=[1, 2], experts_held=[4, 4])
    ref_cfg = dict(_reference_cfg(cfg, shares=2, experts=(4, 4)), heads_held=[1, 2])
    model = get_model("decoder", **cfg)
    params = reference.init_params(jax.random.PRNGKey(9), ref_cfg)
    assert float(jnp.exp(params["layer_0"]["mamba2"]["a_log"][0])) == pytest.approx(5.0)
    tokens, seg = _rows(1)
    with jax.default_matmul_precision("highest"):
        want = reference.logits_of(params, tokens, seg, ref_cfg)
        got, _ = model.apply({"params": params}, tokens, segment_ids=seg, mutable=["counters", "gauges"])
    real = np.asarray(seg) > 0
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real], rtol=2e-4, atol=2e-4)


def test_a_recomputed_model_is_the_same_model():
    cfg = _toy(num_hidden_layers=4, model_layers=8)
    tokens, seg = _rows()
    params = get_model("decoder", **cfg).init(jax.random.PRNGKey(0), tokens, segment_ids=seg)["params"]

    def grads(remat):
        model = get_model("decoder", **dict(cfg, remat=remat))
        return jax.grad(lambda p: jnp.sum(
            model.apply({"params": p}, tokens, segment_ids=seg, mutable=["counters", "gauges"])[0] ** 2))(params)

    for a, b in zip(jax.tree.leaves(grads(False)), jax.tree.leaves(grads(True))):
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * float(jnp.linalg.norm(a)) + 1e-9


@pytest.mark.parametrize("over,match", [
    ({"hybrid_override_pattern": "MEM-EMEM"}, "hybrid_override_pattern"),
    ({"hybrid_override_pattern": "MEM*EME"}, "hybrid_override_pattern"),
    ({"expand": 4}, "expand"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"use_conv_bias": False}, "use_conv_bias"),
    ({"norm_eps": 1e-6}, "norm_eps"),
    ({"mlp_hidden_act": "gelu"}, "mlp_hidden_act"),
    ({"heads_held": [0, 3]}, "heads_held"),
    ({"heads_held": [2, 2]}, "heads_held"),
    ({"heads_held": [0, 2], "attention_bias": True}, "attention_bias"),
    ({"layer_plan": [[None, None, "add"]] * 8}, "unknown layer kinds"),
], ids=["a dense block", "a pattern of another length", "expand", "a projection bias", "no convolution bias",
        "two epsilons", "an unknown activation", "heads that do not divide", "a share past the last",
        "a bias a share", "a block of nothing"])
def test_refused_configurations(over, match):
    with pytest.raises(ValueError, match=match):
        cfg = decoder.DecoderConfig.from_dict(_toy(**over))
        cfg.plan, cfg.heads_plan(0), decoder.Decoder(cfg).init(jax.random.PRNGKey(0), *_rows()[:1])


@pytest.mark.parametrize("key,value", [("moe_latent_size", 32), ("mlp_hidden_act", "relu2")])
@pytest.mark.parametrize("dialect", ["n_routed_experts", "num_experts", "mb_per_layer"])
def test_a_dialect_that_would_ignore_the_latent_or_the_activation_refuses_it_by_name(dialect, key, value):
    """``tie_word_embeddings`` was once ignored in silence (PR 41): a key a
    dialect's layers do not read is refused, by its name."""
    base = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2, num_attention_heads=2, intermediate_size=48)
    configs = {
        "n_routed_experts": dict(base, n_routed_experts=4, num_experts_per_tok=2, moe_intermediate_size=16,
                                 num_key_value_heads=2, head_dim=16),
        "num_experts": dict(base, num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16,
                            num_key_value_heads=2, head_dim=16),
        "mb_per_layer": dict(base, mb_per_layer=2, num_key_value_heads=2, layer_norm_eps=1e-5),
    }
    decoder.DecoderConfig.from_dict(configs[dialect])  # sound without the key
    with pytest.raises(ValueError, match=key):
        decoder.DecoderConfig.from_dict(dict(configs[dialect], **{key: value}))
    if key == "mlp_hidden_act":  # the value that changes nothing is read
        decoder.DecoderConfig.from_dict(dict(configs[dialect], mlp_hidden_act="silu"))


def test_example_trains_the_toy_plan(tmp_path, capsys):
    """``transformer_spark.py --model decoder --model_config nemotron_toy.json``: the example's ``main_fun``
    builds pipeline, model and loss from the published keys, tells the text plane that the model scans, and the
    loss falls."""
    sys.path.insert(0, os.path.join(ROOT, "examples", "transformer"))
    import transformer_spark as example

    with open(os.path.join(ROOT, "examples", "transformer", "nemotron_toy.json")) as f:
        model_cfg = json.load(f)
    data_dir = str(tmp_path / "corpus")
    example.make_text_corpus(data_dir, num_shards=2, records_per_shard=64)
    args = example.build_parser().parse_args([
        "--model", "decoder", "--model_config", "nemotron_toy.json", "--data_dir", data_dir, "--seq_len", "128",
        "--batch_size", "8", "--train_steps", "4", "--log_steps", "1", "--tokenizer", "word", "--dtype", "float32",
        "--attention", "flash_interpret",
    ])
    args.model_cfg = model_cfg
    ctx = types.SimpleNamespace(
        initialize_distributed=lambda: None, num_processes=1, num_workers=1, executor_id=0, distributed=False)
    counters = lambda: obs.snapshot()["counters"]  # noqa: E731
    before = counters().get("ssm_scan_restarts_total", {"value": 0})["value"]
    example.main_fun(args, ctx)
    out = capsys.readouterr().out
    assert "step 4: loss" in out and "transformer training complete" in out
    losses = [float(line.split("loss ")[1].split()[0]) for line in out.splitlines() if line.startswith("step ")]
    assert losses[-1] < losses[0]
    assert counters()["ssm_scan_restarts_total"]["value"] > before
    # two Mamba-2 blocks (E M E M * E), 8 rows of 128 in chunks of 32, every booked step
    assert counters()["ssd_scan_chunks_total"]["value"] % (2 * 8 * 4) == 0 and counters()["ssd_scan_chunks_total"]["value"] > 0


def test_the_scan_runs_per_shard_of_the_batch_on_a_mesh():
    """Two devices over ``dp``: the kernels run under a ``shard_map`` a shard of the rows, and give what one device gives."""
    from jax.sharding import Mesh

    cfg = _toy(num_hidden_layers=3, model_layers=8)  # M E M
    tokens, seg = _rows()
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("dp",))
    alone, meshed = get_model("decoder", **cfg), get_model("decoder", mesh=mesh, **cfg)
    params = alone.init(jax.random.PRNGKey(0), tokens, segment_ids=seg)["params"]

    def loss(model):
        return jax.value_and_grad(lambda p: jnp.sum(
            model.apply({"params": p}, tokens, segment_ids=seg, mutable=["counters", "gauges"])[0] ** 2))(params)

    (one, one_grads), (two, two_grads) = loss(alone), jax.jit(lambda: loss(meshed))()
    assert float(two) == pytest.approx(float(one), rel=1e-5)
    for a, b in zip(jax.tree.leaves(one_grads), jax.tree.leaves(two_grads)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(jnp.linalg.norm(a)) + 1e-9
