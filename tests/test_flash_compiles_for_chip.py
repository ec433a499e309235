"""The flash kernels (and, last, the hyper-connections' four) at the
benchmark's real widths, compiled here for a described v5e (no chip attached; nothing runs, so this says nothing about
results or times). It guards what the Pallas interpreter cannot see: the
scalar-prefetch work list, its index maps and the SMEM reads lowering through
Mosaic, **the traced grid bound** (the batch's longest list: the interpreter
is given the static bound instead), the blocks and the backward's whole-row
dq accumulator fitting the chip's fast memory under the limit the call sets,
and the two kernel names the trace reductions look for.

The topology is described inside a fixture, never while a module is
imported, and only in this file: one process at a time may hold the TPU
library (the ``on-chip-measurement`` guide, section 2).
"""

import os
import re
import sys

import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.ops import flash_blocks
from tensorflowonspark_tpu.ops.flash_attention import flash_attention

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks import trace_reduce  # noqa: E402  (the reduction that has to find the kernels)
from benchmarks.layer_metrics import _program  # noqa: E402  (and the reader that books them to a phase)

ROWS, HEADS, SEQ, HEAD_DIM = 4, 16, 4096, 64  # lm1024.packed4k, one chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: {}".format(e))


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    # a compile for a described device is written to the persistent cache
    # and cannot be read back without a chip: keep it out
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compiled_text(one_chip, segmented, shape=(ROWS, HEADS, SEQ, HEAD_DIM), value_dim=None, **sizes):
    """``shape`` is q's and k's; ``value_dim`` v's head size where it differs."""
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    values = qkv if value_dim is None else jax.ShapeDtypeStruct(shape[:3] + (value_dim,), jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((shape[0], shape[2]), jnp.int32, sharding=one_chip)

    def loss(q, k, v, seg=None):
        o = flash_attention(q, k, v, causal=True, segment_ids=seg, **sizes)
        return (o.astype(jnp.float32) ** 2).sum()

    # the reduction looks for the bare kernel name as the call's last scope.
    # Differentiated bare, the custom_vjp is the outermost scope and its call
    # reads jvp(flash_fwd_seg); jax.checkpoint, or any module round the call
    # (recomputed or not: the models' tests below), takes the transform's name
    grad = jax.jit(jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2)))
    lowered = grad.lower(qkv, qkv, values, ids) if segmented else grad.lower(qkv, qkv, values)
    return lowered.compile().as_text()


def _kernels(text):
    """The Mosaic custom calls' kernel names, read as the benchmark's trace
    reduction reads them from the compiled module."""
    return sorted(trace_reduce.kernel_names(text).values())


def _walks_a_list_of(text, items):
    """Both kernels' custom calls take a traced scalar (the grid bound: the
    batch's longest list) and then one table of ``items`` int32 (every batch
    row's work list, the shape's bound a row), and no second table."""
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    opening = "operand_layout_constraints={{s32[], s32[{}]{{0}}, bf16[".format(items)
    return len(calls) == 2 and all(opening in line for line in calls)


@pytest.mark.parametrize("blocks", [
    {},  # the constants the program runs with
    {"block_q": 512, "block_k": 512}, {"block_q": 512, "block_k": 256}, {"block_q": 256, "block_k": 256},
], ids=["defaults", "512x512", "512x256", "256x256"])
def test_segmented_kernels_compile_at_the_cells_widths(one_chip, no_compile_cache, blocks):
    text = _compiled_text(one_chip, True, **blocks)
    # exactly the two, one call each: a third kernel would be undercounted
    assert _kernels(text) == ["flash_bwd_dkv_seg", "flash_fwd_seg"]
    # the grid walks a list as long as the causal triangle of the blocks, not their square
    n_q, n_k = SEQ // blocks.get("block_q", 512), SEQ // blocks.get("block_k", 512)
    triangle = int(flash_blocks.causal_blocks(n_q, n_k, SEQ // n_q, SEQ // n_k).sum())
    assert triangle < n_q * n_k and _walks_a_list_of(text, ROWS * triangle)
    # per head the operands are the benchmark's; the ids ride per batch row
    assert "bf16[{},{},{}]".format(ROWS * HEADS, SEQ, HEAD_DIM) in text
    assert "s32[{},{},128]".format(ROWS, SEQ) in text  # the query side's, an id in every lane
    assert "s32[{},{},128]".format(ROWS * HEADS, SEQ) not in text
    assert "s32[{},8,{}]".format(ROWS * HEADS, SEQ) not in text


def test_segmented_kernels_compile_at_latent_attention_widths(one_chip, no_compile_cache):
    """``xing4-a4b.packed8k``: one row of 8192, 32 heads, queries and keys 192
    wide (not a multiple of the 128 lanes) against values of 128. The dq row
    alone (8 MiB float32 + the output block's buffers) is over Mosaic's
    default 16 MiB: this is where the call's own VMEM limit is proved."""
    text = _compiled_text(one_chip, True, shape=(1, 32, 8192, 192), value_dim=128)
    assert _kernels(text) == ["flash_bwd_dkv_seg", "flash_fwd_seg"]
    assert "bf16[32,8192,192]" in text and "bf16[32,8192,128]" in text
    assert _walks_a_list_of(text, 136)  # 16 x 17 / 2 of 256 blocks


def test_segmented_kernels_compile_at_a_value_head_of_256(one_chip, no_compile_cache):
    """Values twice a register's lanes wide, the widest the module documents:
    the forward's lane-wide row statistics meet an accumulator of two
    registers a row (``pltpu.repeat``), the backward a dv block as wide."""
    text = _compiled_text(one_chip, True, shape=(1, 8, SEQ, 128), value_dim=256)
    assert _kernels(text) == ["flash_bwd_dkv_seg", "flash_fwd_seg"]
    assert "bf16[8,4096,128]" in text and "bf16[8,4096,256]" in text
    assert _walks_a_list_of(text, 36)


def test_segmented_kernels_compile_at_one_row_of_16k(one_chip, no_compile_cache):
    """PERF.md §7's ``lm1024.packed16k``: one packed row of 16,384."""
    text = _compiled_text(one_chip, True, shape=(1, 16, 16384, 64))
    assert _kernels(text) == ["flash_bwd_dkv_seg", "flash_fwd_seg"]
    assert "bf16[16,16384,64]" in text
    assert _walks_a_list_of(text, 528)


def test_unsegmented_kernels_compile_and_keep_their_names(one_chip, no_compile_cache):
    text = _compiled_text(one_chip, False)
    assert _kernels(text) == ["flash_bwd_dkv", "flash_fwd"]
    assert _walks_a_list_of(text, 36)  # one list for all rows and heads: the triangle


def test_longest_row_the_lists_allow_compiles(one_chip, no_compile_cache):
    """One row of 81,920 positions, the longest the backward's VMEM limit
    takes: its work list is 12,880 items, 50 KiB of SMEM."""
    text = _compiled_text(one_chip, True, shape=(1, 1, 81920, 64))
    assert _kernels(text) == ["flash_bwd_dkv_seg", "flash_fwd_seg"]
    assert _walks_a_list_of(text, 12880)


def test_block_diffusion_kernels_compile_at_the_cells_widths(one_chip, no_compile_cache):
    """``sdar-30b-a3b.bd4-packed4k``: two rows of 8192 positions (a clean and a
    noised copy of 4096), 32 query heads over 4 key/value heads of 128, under
    the block-diffusion rule. What only the chip's compiler can say: the
    grouped backward's three-dimensional grid, its whole-row dk and dv scratch
    beside the dq row (40 MiB of VMEM under the limit the call sets), the
    marks riding where the ids ride (the backward fetches 768 KiB of them a
    step), and the key/value operands at 4 heads:
    nothing repeated in HBM."""
    q = jax.ShapeDtypeStruct((2, 32, 8192, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 4, 8192, 128), jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)

    def loss(q, k, v, seg, labels):
        o = flash_attention(q, k, v, segment_ids=seg, labels=labels, rule="block_diffusion")
        return (o.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2))).lower(q, kv, kv, ids, ids).compile().as_text()
    assert _kernels(text) == ["flash_bwd_dkv_bd", "flash_fwd_bd"]
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    # one table of 2 rows x the square of 16 blocks, then q at 32 heads and k, v at 4
    # the query side's three marks a position, 128 lanes each (a row's value in every lane)
    opening = "operand_layout_constraints={s32[], s32[512]{0}, bf16[64,8192,128]{2,1,0}, bf16[8,8192,128]{2,1,0}, bf16[8,8192,128]{2,1,0}, s32[2,8192,384]"
    assert len(calls) == 2 and all(opening in line for line in calls)
    backward = next(line for line in calls if "flash_bwd_dkv_bd" in line)
    # dq at the 32 query heads, dk and dv at the 4 key/value heads: summed over the group inside the kernel
    assert "(bf16[64,8192,128]{2,1,0:T(8,128)(2,1)}, bf16[8,8192,128]{2,1,0:T(8,128)(2,1)}, bf16[8,8192,128]{2,1,0:T(8,128)(2,1)})" in backward


def test_window_kernels_compile_at_the_cells_widths(one_chip, no_compile_cache):
    """``laguna-s-2-1.code8k``'s sliding layers: one row of 8192, 72 query
    heads over 8 key/value heads of 128 (nine a group through the grouped
    backward: the dq row beside whole-row dk and dv, 40 MiB of VMEM under the
    limit the call sets), under the window rule at 512. The lists' stride is
    the band, 31 blocks of the triangle's 136, and the kernels bear the names
    the ``swa_*`` readers find them by; the full layers' call at 48 over 8 keeps
    the causal names and the triangle."""
    q = jax.ShapeDtypeStruct((1, 72, 8192, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8, 8192, 128), jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)

    def loss(q, k, v, seg, **rule):
        o = flash_attention(q, k, v, causal=True, segment_ids=seg, **rule)
        return (o.astype(jnp.float32) ** 2).sum()

    def compiled(q, **rule):
        step = jax.jit(jax.grad(jax.checkpoint(lambda *a: loss(*a, **rule)), argnums=(0, 1, 2)))
        return step.lower(q, kv, kv, ids).compile().as_text()

    text = compiled(q, rule="window", window=512)
    assert _kernels(text) == ["flash_bwd_dkv_win", "flash_fwd_win"]
    assert _walks_a_list_of(text, 31)  # the diagonal's 16 blocks and the 15 beside it
    backward = next(line for line in text.splitlines() if "flash_bwd_dkv_win" in line and "tpu_custom_call" in line)
    assert "(bf16[72,8192,128]{2,1,0:T(8,128)(2,1)}, bf16[8,8192,128]{2,1,0:T(8,128)(2,1)}, bf16[8,8192,128]{2,1,0:T(8,128)(2,1)})" in backward
    full = compiled(jax.ShapeDtypeStruct((1, 48, 8192, 128), jnp.bfloat16, sharding=one_chip))
    assert _kernels(full) == ["flash_bwd_dkv_seg", "flash_fwd_seg"] and _walks_a_list_of(full, 136)
    assert "bf16[48,8192,128]" in full and "bf16[8,8192,128]" in full


def _model_step(one_chip, model, rows, seq):
    """``(compiled loss-and-gradient of ``model`` on packed rows, its compiler's text)``,
    under the scope the train step gives it."""
    from tensorflowonspark_tpu.models import transformer

    on_chip = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)  # noqa: E731
    ids = jax.ShapeDtypeStruct((rows, seq + 1), jnp.int32, sharding=one_chip)
    batch = {"tokens": ids, "segment_ids": ids, "positions": ids}
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))["params"]))
    loss_fn = transformer.make_loss_fn(model)

    def step(params, batch):
        with jax.named_scope("tos.loss_and_grad"):
            return jax.value_and_grad(lambda p: loss_fn(p, batch)[0])(params)

    compiled = jax.jit(step).lower(params, batch).compile()
    return compiled, compiled.as_text()


def _kept_a_layer(one_chip, monkeypatch, build, layers, rows, seq):
    """What the recomputation's policy costs a layer, in bytes of the step's
    temporaries, and the text of the program with the policy."""
    from tensorflowonspark_tpu.models import decoder, transformer

    # the models refuse attention="flash" off the chip; this compiles for one
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, text = _model_step(one_chip, build(), rows, seq)
    for module in (transformer, decoder):
        monkeypatch.setattr(module, "REMAT_POLICY", None)
    plain, plain_text = _model_step(one_chip, build(), rows, seq)
    assert _kernels(plain_text).count("flash_fwd_seg") == 2 * layers
    grown = compiled.memory_analysis().temp_size_in_bytes - plain.memory_analysis().temp_size_in_bytes
    return grown / layers, text


def _recomputed_attention_products(text):
    """The attention products of the compiled text's recomputed passes whose
    results a recomputed layer keeps: ``op_name``s under
    ``rematted_computation`` that are a ``dot_general`` of the projections
    (under latent attention the down-projections; its up-projections run
    again), whatever scope the attention's module opens round them."""
    names = set(re.findall(r'op_name="([^"]*rematted_computation[^"]*)"', text))
    return sorted(name for name in names if re.search(r"attn/(tos\.\w+/)?(q|k|v|o|q_a|kv_a)/dot_general", name))


def _forward_calls_are_bare_and_not_recomputed(text, layers):
    assert _kernels(text) == ["flash_bwd_dkv_seg"] * layers + ["flash_fwd_seg"] * layers
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    names = [re.search(r'op_name="([^"]*)"', line).group(1) for line in calls]
    forward = [name for name in names if "flash_fwd_seg" in name]
    assert len(forward) == layers and all(name.endswith("/flash_fwd_seg/pallas_call") for name in forward)
    assert not any("rematted_computation" in name for name in names)
    # the phases the benchmark's readers book them to
    assert [_program.phase_of(name) for name in forward] == ["fwd"] * layers
    assert [_program.phase_of(name) for name in names if name not in forward] == ["bwd"] * layers


def test_recomputed_transformer_runs_the_forward_kernel_once_a_layer(one_chip, no_compile_cache, monkeypatch):
    """``lm1024.packed4k``'s blocks, two of them: the recomputed pass keeps
    ``o`` and the log-sum-exp, so each layer's forward kernel is called once,
    under the bare name the trace reductions look for and in the forward
    phase. ``o`` is kept with its heads merged, ``[rows, L, 1024]``: 33.5 MB a
    layer and 1 MB of log-sum-exp (the kernel's ``[batch·heads, L, 64]`` pads
    its 64 lanes to 128, 67 MB). It also keeps q, k and v as the projections
    return them and the sub-layer's result, 4 x 33.5 MB more: about 168 MB a
    layer in all, and the recomputed pass runs none of the four products."""
    from tensorflowonspark_tpu.models import transformer

    layers = 2
    build = lambda: transformer.create_model(  # noqa: E731
        vocab_size=50304, d_model=1024, n_layers=layers, n_heads=HEADS, d_ff=4096, max_seq_len=SEQ,
        dtype="bfloat16", remat=True, attention="flash")
    kept, text = _kept_a_layer(one_chip, monkeypatch, build, layers, ROWS, SEQ)
    _forward_calls_are_bare_and_not_recomputed(text, layers)
    assert not _recomputed_attention_products(text)
    assert 150e6 < kept < 185e6


def test_recomputed_decoder_runs_the_forward_kernel_once_a_layer(one_chip, no_compile_cache, monkeypatch):
    """``xing4-a4b.packed8k``'s latent attention (192/128, one row of 8192) in
    two layers of ``mla`` + ``swiglu`` on one stream: values of 128 pad nothing,
    so a layer keeps ``o``'s 67 MB and 1 MB of log-sum-exp, and beside them
    the two latents (768 + 576 values a token, 22 MB) and the sub-layer's
    result (58.7 MB): about 150 MB a layer by the count, 110 MB by the
    compiler's temporaries, because the recomputed pass's own peak shrinks
    with the products it no longer runs. It runs the up-projections again
    and neither down-projection nor the output's."""
    import json

    from benchmarks.families import moe_lm
    from tensorflowonspark_tpu.models import get_model

    layers = 2
    with open(os.path.join(os.path.dirname(trace_reduce.__file__), "configs", "xing4-a4b.json")) as f:
        cfg = moe_lm.model_config(json.load(f), remat=True)
    cfg.update(num_hidden_layers=layers, first_k_dense_replace=layers, hc_mult=1, attention="flash")
    kept, text = _kept_a_layer(one_chip, monkeypatch, lambda: get_model("decoder", **cfg), layers, 1, 8192)
    _forward_calls_are_bare_and_not_recomputed(text, layers)
    assert "bf16[32,8192,192]" in text and "bf16[32,8192,128]" in text
    assert not _recomputed_attention_products(text)
    assert 95e6 < kept < 125e6


def test_scan_kernels_compile_at_the_cells_shape(one_chip, no_compile_cache):
    """``phi-4-mini-flash.reason8k``'s selective scan: one row of 8192, 5120
    channels by 16 states, bfloat16 operands, the state float32 in VMEM:
    chunks of 256, channel blocks of 512 forward and 256 backward (the
    chunk's states, ``[257, 16, 256]`` float32, beside the widened operands'
    blocks: under the 64 MiB the calls ask for), the kernels under the names
    the readers find them by, the boundary states ``[1, 32, 16, 5120]``."""
    from tensorflowonspark_tpu.ops.selective_scan import selective_scan

    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    wide, narrow = on_chip((1, 8192, 5120), jnp.bfloat16), on_chip((1, 8192, 16), jnp.bfloat16)
    args = (wide, wide, narrow, narrow, on_chip((5120, 16), jnp.float32), on_chip((5120,), jnp.float32))

    def loss(dt, x, b, c, a, skip, ids):
        return (selective_scan(dt, x, b, c, a, skip, ids).astype(jnp.float32) ** 2).sum()

    step = jax.jit(jax.grad(jax.checkpoint(loss), argnums=tuple(range(6))))
    text = step.lower(*args, on_chip((1, 8192), jnp.int32)).compile().as_text()
    assert _kernels(text) == ["ssm_scan_bwd", "ssm_scan_fwd"]
    assert "f32[1,32,16,5120]" in text  # a state a chunk, not a position
    assert "f32[1,8192,16,128]" in text  # B and C ride widened to the lanes
    assert "f32[1,8192,16,5120]" not in text and "f32[1,8192,5120,16]" not in text  # no state a position in HBM


def test_hybrid_decoder_compiles_at_the_cells_shape(one_chip, no_compile_cache, monkeypatch):
    """``phi-4-mini-flash.reason8k`` whole, loss and gradients, one row of
    8192 recomputed: two scans, one windowed differential layer (40 query
    heads in groups of 2 over 20 key heads of 64, each under a value of 128)
    and the full and the cross layer through the causal kernels; every
    forward kernel once (the scan's results and the flash kernels' are what
    a recomputed layer keeps), none inside a recomputed pass; the step's
    temporaries leave room beside 8.4 GB of parameters and moments."""
    import json

    from benchmarks.families import ssm_lm
    from tensorflowonspark_tpu.models import get_model

    with open(os.path.join(os.path.dirname(trace_reduce.__file__), "configs", "phi-4-mini-flash.json")) as f:
        cfg = ssm_lm.model_config(json.load(f), remat=True)
    cfg.update(attention="flash")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, text = _model_step(one_chip, get_model("decoder", **cfg), 1, 8192)
    assert _kernels(text) == sorted(
        ["ssm_scan_fwd", "ssm_scan_bwd"] * 2 + ["flash_fwd_win", "flash_bwd_dkv_win"]
        + ["flash_fwd_seg", "flash_bwd_dkv_seg"] * 2)
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    names = [re.search(r'op_name="([^"]*)"', line).group(1) for line in calls]
    assert not any("rematted_computation" in name for name in names)
    forward = [name for name in names if "_fwd" in name]
    assert [_program.phase_of(name) for name in forward] == ["fwd"] * 5
    assert [_program.phase_of(name) for name in names if name not in forward] == ["bwd"] * 5
    assert all("tos.ssm_scan" in name for name in names if "ssm_scan" in name)
    assert "bf16[40,8192,64]" in text and "bf16[20,8192,64]" in text and "bf16[20,8192,128]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 5.5e9


def test_ssd_scan_kernels_compile_at_the_cells_shape(one_chip, no_compile_cache):
    """``nemotron-3-super.agent8k``'s Mamba-2 scan: one row of 8192 in chunks
    of 128, 32 heads of 64 channels in 2 groups of 128 states, bfloat16
    operands, decays and state float32: one grid step a chunk and group (16
    heads walked in it), the kernels under the names the readers find them
    by, a state a chunk and head ``[1, 32, 64, 64, 128]`` and none a position."""
    from tensorflowonspark_tpu.ops.ssd_scan import ssd_scan

    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    narrow, heads = on_chip((1, 8192, 2, 128), jnp.bfloat16), on_chip((32,), jnp.float32)
    args = (on_chip((1, 8192, 32, 64), jnp.bfloat16), on_chip((1, 8192, 32), jnp.float32), heads, narrow, narrow, heads)

    def loss(x, delta, a, b, c, skip, ids):
        return (ssd_scan(x, delta, a, b, c, skip, ids).astype(jnp.float32) ** 2).sum()

    step = jax.jit(jax.grad(jax.checkpoint(loss), argnums=tuple(range(6))))
    text = step.lower(*args, on_chip((1, 8192), jnp.int32)).compile().as_text()
    assert _kernels(text) == ["ssd_scan_bwd", "ssd_scan_fwd"]
    assert "f32[1,32,64,64,128]" in text  # a state a chunk
    assert "f32[1,8192,32,64,128]" not in text and "f32[1,32,8192,64,128]" not in text  # none a position


def test_one_sub_layer_decoder_compiles_at_the_cells_shape(one_chip, no_compile_cache, monkeypatch):
    """``nemotron-3-super.agent8k`` whole, loss and gradients, one row of 8192
    recomputed: five chunked scans, one attention block of 8 query heads on 1
    key/value head through the causal kernels, five expert blocks on the
    compact slot buffer in the latent's width; every forward kernel once (the
    scans' and the flash kernels' results are what a recomputed block keeps),
    none inside a recomputed pass; the step's temporaries leave room beside
    9.3 GB of parameters and moments."""
    import json

    from benchmarks.families import ssd_lm
    from tensorflowonspark_tpu.models import get_model

    with open(os.path.join(os.path.dirname(trace_reduce.__file__), "configs", "nemotron-3-super.json")) as f:
        cfg = ssd_lm.model_config(json.load(f), remat=True)
    cfg.update(attention="flash")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, text = _model_step(one_chip, get_model("decoder", **cfg), 1, 8192)
    kernels = _kernels(text)
    assert [k for k in kernels if k.startswith(("ssd", "flash"))] == sorted(
        ["ssd_scan_fwd", "ssd_scan_bwd"] * 5 + ["flash_fwd_seg", "flash_bwd_dkv_seg"])
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    names = [re.search(r'op_name="([^"]*)"', line).group(1) for line in calls]
    scans = [name for name in names if "ssd_scan" in name]
    assert len(scans) == 10 and all("tos.mamba2/tos.ssd_scan" in name for name in scans)
    assert not any("rematted_computation" in name for name in scans)
    assert sorted(_program.phase_of(name) for name in scans) == ["bwd"] * 5 + ["fwd"] * 5
    assert "bf16[8,8192,128]" in text and "bf16[1,8192,128]" in text  # 8 query heads on 1 key/value head
    assert "bf16[5632,1024]" in text  # the compact slot buffer, in the latent's width
    assert compiled.memory_analysis().temp_size_in_bytes < 5.5e9


def test_hyper_connection_kernels_compile_at_the_cells_shape(one_chip, no_compile_cache):
    """``xing4-a4b.packed8k``'s residual path (kept in this file: one process
    may hold the TPU library): one row of 8192 tokens, four streams of 3584
    in bfloat16, forward and backward of both passes. Tiles of 256 whole
    rows (7 MiB a block of the streams, up to three of them double-buffered
    and a float32 scratch of the same rows in the maps' backward) have to fit
    the VMEM limit the calls set, the chunk loops' dynamic lane slices have
    to lower, and the four kernels keep the names the trace shows."""
    from tensorflowonspark_tpu.ops import hyper_connection

    tokens, n, d = 8192, 4, 3584
    shape = lambda width, dtype=jnp.bfloat16: jax.ShapeDtypeStruct((1, tokens, width), dtype, sharding=one_chip)  # noqa: E731
    whole = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)  # noqa: E731

    def loss(streams, y, maps, phi, alpha_pre, b_pre):
        h, z, streams = hyper_connection.read(streams, phi, alpha_pre, b_pre)
        out = hyper_connection.merge(streams, y + h, maps + z[..., :n * n + n])  # every input and result differentiated
        return (out.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        shape(n * d), shape(d), shape(n * n + n, jnp.float32), whole(n, d, 2 * n + n * n), whole(), whole(n)
    ).compile().as_text()
    assert _kernels(text) == ["mhc_merge", "mhc_merge_bwd", "mhc_read", "mhc_read_bwd"]
    assert "bf16[{},{}]".format(tokens, n * d) in text


def _computations(text):
    """``{name: [lines]}`` of a compiled module's computations."""
    found, lines = {}, None
    for line in text.splitlines():
        opened = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$", line)
        if opened:
            lines = found.setdefault(opened.group(1), [])
        elif lines is not None:
            lines.append(line)
    return found


def _reached(computations, name, seen, fused=False):
    """The lines of ``name`` and of every computation it calls; what a line
    inside a fusion defines is never written to memory, so those are left out
    unless ``fused``."""
    if name in seen or name not in computations or (name.startswith("fused_computation") and not fused):
        return []
    seen.add(name)
    lines = list(computations[name])
    for line in computations[name]:
        for callee in re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line):
            lines += _reached(computations, callee, seen, fused)
    return lines


#: the three routed cells: configuration, family, rows and positions a row (``bd4-packed4k`` reads 8192 a row of 4096),
#: whether the layer is told the segment ids (padding positions routed nowhere)
ROUTED_CELLS = {
    "sdar-30b-a3b.bd4-packed4k": ("sdar-30b-a3b", "bd_lm", 2, 8192, False),
    "laguna-s-2-1.code8k": ("laguna-s-2-1", "swa_lm", 1, 8192, True),
    "xing4-a4b.packed8k": ("xing4-a4b", "moe_lm", 1, 8192, False),
}


@pytest.fixture(scope="module")
def routed_layer(one_chip, no_compile_cache):
    """``cell -> (cfg, tokens, computations)``: one routed layer of a routed
    cell at the cell's shape, forward and backward under ``jax.checkpoint``,
    compiled once a cell for all the tests that read its text."""
    import functools
    import importlib
    import json

    from tensorflowonspark_tpu.models import decoder

    @functools.lru_cache(maxsize=None)
    def compiled(cell):
        config, family, rows, seq, segmented = ROUTED_CELLS[cell]
        with open(os.path.join(os.path.dirname(trace_reduce.__file__), "configs", config + ".json")) as f:
            cfg = decoder.DecoderConfig.from_dict(
                importlib.import_module("benchmarks.families." + family).model_config(json.load(f), remat=True))
        d = cfg.hidden_size
        layer = decoder.RoutedExperts(cfg)
        on_chip = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)  # noqa: E731
        x = jax.ShapeDtypeStruct((rows, seq, d), jnp.bfloat16, sharding=one_chip)
        ids = (jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=one_chip),) if segmented else ()
        params = jax.tree.map(on_chip, jax.eval_shape(
            lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 128, d), jnp.bfloat16))["params"]))

        def loss(p, x, *ids):
            return jnp.sum(layer.apply({"params": p}, x, *ids)[0].astype(jnp.float32) ** 2)

        def step(p, x, *ids):
            with jax.named_scope("tos.loss_and_grad"):
                return jax.value_and_grad(jax.checkpoint(loss), argnums=(0, 1))(p, x, *ids)

        with pytest.MonkeyPatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")  # the kernel, not its interpreter
            return cfg, rows * seq, _computations(jax.jit(step).lower(params, x, *ids).compile().as_text())

    return compiled


@pytest.mark.parametrize("cell", list(ROUTED_CELLS))
def test_routed_layer_compiles_a_compact_branch_at_the_cells_shape(routed_layer, cell):
    """One routed layer of each routed cell at the cell's shape
    (``sdar-30b-a3b``: 2 x 8192 positions, top-8 of 128, 16 held: 131,072
    slots, a compact buffer of 32,768; ``laguna-s-2-1``: 8192, top-10 of 256,
    8 held, 5,120 rows; ``xing4-a4b``: 8192, top-4 of 64, 8 held, 8,192 rows),
    forward and backward under ``jax.checkpoint``. Three conditionals (the
    forward pass, the recomputed one because this loss reads the layer's
    result again, the backward one); in each, the branch for the compact
    buffer holds no array of the bound's length as wide as the model or an
    expert, and nor does the other, the fallback, which runs a share of the
    tokens at a time. The way back to token order is the ``moe_combine``
    kernel under ``tos.moe_route`` in every compact branch (forward,
    recomputed and backward alike, once each): no copy of the buffer with a zero row behind
    it (``C + 1`` rows) and no gather that writes ``[T, d]`` is left there.
    In both branches the grouped products keep the bare name the
    readers find them by and every other kernel call sits under one of the
    mechanisms' scopes (the fallback's second sort too); the operations fall into the phases they fell into
    (the forward pass that the backward branch runs again is booked with it);
    and the ``conditional`` instructions carry none of the mechanisms'
    scopes, so that a reader which sums events by scope counts a branch's
    operations once."""
    from benchmarks.layer_metrics import _moe
    from tensorflowonspark_tpu.ops import grouped_matmul as gm

    cfg, tokens, computations = routed_layer(cell)
    d, width = cfg.hidden_size, cfg.moe_intermediate_size
    slots = tokens * cfg.num_experts_per_tok
    buffer = gm.compact_rows(slots, cfg.held[1], cfg.n_routed_experts)
    assert buffer < slots
    conditionals = [line for lines in computations.values() for line in lines if " conditional(" in line]
    assert len(conditionals) == 3
    long_and_wide = re.compile(r"= \(?(?:bf16|f32|pred)\[{},({}|{})\]".format(slots, d, width))
    zero_row_behind = re.compile(r"\[{},{}\]".format(buffer + 1, d))
    gathers_token_rows = re.compile(r"= \(?(?:bf16|f32)\[{},{}\]\S* gather\(".format(tokens, d))
    phases, combines = [], []
    for line in conditionals:
        scope = re.search(r'op_name="([^"]*)"', line)
        assert scope is None or "tos.moe_" not in scope.group(1)
        every_slot, compact = (name.strip().lstrip("%") for name in re.search(
            r"branch_computations=\{([^}]*)\}", line).group(1).split(","))  # cond(fits, compact, fallback): true is last
        booked = []
        for branch, scope in ((compact, "compact_rows"), (every_slot, "every_slot")):
            inside = [ln for ln in _reached(computations, branch, set()) if " parameter(" not in ln]
            assert not [ln for ln in inside if long_and_wide.search(ln)]
            assert any(" while(" in ln for ln in inside) == (branch == every_slot)
            named = [re.search(r'op_name="([^"]*)"', ln).group(1) for ln in inside
                     if ("custom-call(" in ln or "kind=kCustom" in ln) and "op_name" in ln]
            products = [name for name in named if _moe.is_grouped_product(name)]
            assert products and set(products) == {"ragged-dot-none", "ragged-dot-metadata"}
            scoped = [name for name in named if _moe.in_scope(name, "tos.moe_route") or _moe.in_scope(name, "tos.moe_experts")]
            # every kernel call is a product's or under a scope (the fallback's loop fills a result it first zeroes)
            rest = [name for name in named if name not in products and name not in scoped]
            assert scoped and all(branch == every_slot and name.endswith("/broadcast_in_dim") for name in rest)
            assert all("/" + scope in name or "(" + scope + ")" in name for name in scoped)
            booked.append({_program.phase_of(name) for name in scoped})
            kernel = [name for name in named if "/moe_combine" in name]
            assert all(_moe.in_scope(name, "tos.moe_route") for name in kernel)
            if branch == compact:
                combines.append(len(kernel))
                whole = _reached(computations, branch, set(), fused=True)
                assert not [ln for ln in whole if zero_row_behind.search(ln)]
                if buffer != tokens:  # where they are equal the dispatch's gather of [C, d] has the same shape
                    assert not [ln for ln in whole if gathers_token_rows.search(ln)]
            else:
                assert not kernel  # a share's slots all fit its buffer: one gather back to slot order
        assert booked[1] - booked[0] <= {"recompute"}  # the fallback's backward pass runs each share again, and says so
        phases.append(booked[0])
    assert sorted(phases, key=sorted) == [{"bwd"}, {"fwd"}, {"recompute"}]
    # forward; recomputed; the dispatch's gradient (the sum that the backward branch's forward pass ends in feeds nothing)
    assert combines == [1, 1, 1]


@pytest.mark.parametrize("cell", list(ROUTED_CELLS))
def test_routed_layers_bookkeeping_compiles_to_no_gather_and_no_scatter(routed_layer, cell):
    """The same compiled layer, everything but the fallback's computations
    (the router, the sort and the three compact branches): the chosen scores
    are a select and a reduction over ``[T, k, E]`` that stays inside one
    fusion forward, recomputed and backward (no array of that shape is written:
    67 MB a layer in ``sdar-30b-a3b``, 336 MB in ``laguna-s-2-1``), so no gather
    reads ``[T, E]`` and no scatter-add writes it; the inverse of the sort's
    order is not computed (no int32 scatter). What is left walks an index at a
    time for a reason: the dispatch's row gather ``[C, d]`` from ``[T, d]``
    and its gradient's, the weights' gather ``float32 [C]`` from ``[T * k]``
    and that gather's transpose, one scatter-add into ``float32 [T * k]``."""
    from tensorflowonspark_tpu.ops import grouped_matmul as gm

    cfg, tokens, computations = routed_layer(cell)
    k, experts, d = cfg.num_experts_per_tok, cfg.n_routed_experts, cfg.hidden_size
    slots = tokens * k
    buffer = gm.compact_rows(slots, cfg.held[1], cfg.n_routed_experts)
    fallback = set()
    for line in (line for lines in computations.values() for line in lines if " conditional(" in line):
        every_slot = re.search(r"branch_computations=\{%?([\w.\-]+),", line).group(1)  # cond(fits, compact, fallback): false is first
        _reached(computations, every_slot, fallback, fused=True)
    assert any(" scatter(" in line and "s32[" in line for name in fallback for line in computations[name])  # a share's own place
    outside = {name: lines for name, lines in computations.items() if name not in fallback}
    walked = set()
    for line in (line for lines in outside.values() for line in lines):
        found = re.search(r"= \(?(\w+\[[\d,]*\])\S* (gather|scatter)\(", line)
        if found:
            assert "tos.moe_route" in line
            walked.add((found.group(2), found.group(1)))
    assert walked == {("gather", "bf16[{},{}]".format(buffer, d)), ("gather", "f32[{}]".format(buffer)),
                      ("scatter", "f32[{}]".format(slots))}
    per_expert = re.compile(r"= \(?\w+\[{},{},{}\]".format(tokens, k, experts))
    assert any(per_expert.search(line) for lines in outside.values() for line in lines)  # the mask is there, fused
    assert not [line for name, lines in outside.items() if not name.startswith("fused_computation")
                for line in lines if per_expert.search(line)]


def test_routed_layer_compiles_for_four_chips(topo, no_compile_cache, monkeypatch):
    """JAX refuses to lower a Mosaic call on more than one chip outside a
    ``shard_map`` ("Mosaic kernels cannot be automatically partitioned"), which
    no interpreted test sees. One routed layer of ``sdar-30b-a3b`` on the
    described host's 2 x 2 mesh, the batch over ``dp``: the layer is told its
    mesh, and the kernel runs on every chip on all the tokens and half the
    columns (``tp`` 2), forward and backward."""
    import json

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.families import bd_lm
    from tensorflowonspark_tpu.models import decoder

    with open(os.path.join(os.path.dirname(trace_reduce.__file__), "configs", "sdar-30b-a3b.json")) as f:
        cfg = decoder.DecoderConfig.from_dict(bd_lm.model_config(json.load(f), remat=True))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))
    rows, seq, d = 4, 2048, cfg.hidden_size
    layer = decoder.RoutedExperts(cfg, mesh)
    x = jax.ShapeDtypeStruct((rows, seq, d), jnp.bfloat16, sharding=NamedSharding(mesh, P("dp", None, None)))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, P())),
        jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 128, d), jnp.bfloat16))["params"]))

    def loss(p, x):
        return jnp.sum(layer.apply({"params": p}, x)[0].astype(jnp.float32) ** 2)

    def step(p, x):
        return jax.value_and_grad(loss, argnums=(0, 1))(p, x)

    text = jax.jit(step).lower(params, x).compile().as_text()
    calls = [line for line in text.splitlines() if "custom-call(" in line and "/moe_combine" in line]
    assert len(calls) == 2 and all("= bf16[{},{}]".format(rows * seq, d // 2) in line for line in calls)


@pytest.mark.parametrize("cell", ["lm1024.packed4k", "phi-4-mini-flash.reason8k"])
def test_a_model_without_routed_layers_traces_none_of_the_routing(monkeypatch, cell):
    """The loss and gradients of the two language-model cells that have no
    routed layer, traced at their cells' shapes with the routed layer and
    everything of ``ops/grouped_matmul.py`` and ``ops/moe_combine.py``
    replaced by a function that raises: they reach none of it, so what PRs 42
    and 43 changed there leaves their jaxprs what they were."""
    from benchmarks import run
    from benchmarks.families import ssm_lm
    from tensorflowonspark_tpu.models import decoder, get_model, transformer
    from tensorflowonspark_tpu.ops import grouped_matmul, moe_combine

    def reached(*args, **kwargs):
        raise AssertionError("a model without routed layers reached the routed experts' code")

    for module in (grouped_matmul, moe_combine):
        for name, value in list(vars(module).items()):
            if callable(value) and getattr(value, "__module__", None) == module.__name__:
                monkeypatch.setattr(module, name, reached)
    monkeypatch.setattr(decoder.RoutedExperts, "__call__", reached)
    monkeypatch.setattr(decoder, "_experts_on_rows", reached)
    monkeypatch.setattr(decoder, "_scores_at", reached)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the models refuse attention="flash" off the chip
    _, _, config, traffic = run.resolve(cell, False)
    if config["family"] == "lm":  # as ``benchmarks/families/lm.build`` makes it
        model = transformer.create_model(
            vocab_size=config["vocab_size"], d_model=config["d_model"], n_layers=config["n_layers"],
            n_heads=config["n_heads"], d_ff=config["d_ff"], max_seq_len=traffic["seq_len"], dtype=config["dtype"],
            remat=traffic["remat"], attention="flash")
    else:
        model = get_model("decoder", **dict(ssm_lm.model_config(config, remat=traffic["remat"]), attention="flash"))
    rows, seq = traffic["batch_per_chip"], traffic["seq_len"]
    ids = jax.ShapeDtypeStruct((rows, seq + 1), jnp.int32)
    batch = {"tokens": ids, "segment_ids": ids, "positions": ids}
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))["params"])
    loss_fn = transformer.make_loss_fn(model)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(lambda p, b: loss_fn(p, b)[0]))(params, batch)
    text = str(jaxpr)
    assert "pallas_call" in text and "moe_combine" not in text and "ragged_dot" not in text


def test_defaults_are_the_segmented_constants():
    assert flash_blocks.pick_block(SEQ, flash_blocks.SEGMENTED_BLOCK_Q) == flash_blocks.SEGMENTED_BLOCK_Q
    assert flash_blocks.pick_block(SEQ, flash_blocks.SEGMENTED_BLOCK_K) == flash_blocks.SEGMENTED_BLOCK_K
