"""Flash attention as pallas TPU kernels, with a full custom VJP.

Blockwise attention that never materializes the [L, L] score matrix: the
forward streams K/V blocks through VMEM accumulating an online softmax
(running max ``m``, denominator ``l``, weighted values ``acc``); the backward
recomputes probabilities per block from the saved log-sum-exp and accumulates
dq / dk / dv. Two kernels a call, one forward and one backward, while HBM
traffic stays O(L·D).

**What a computed block issues** (PR 37; ``scripts/flash_bundles.py`` reads
it from the chip's compiler, PERF.md §7 has the tables). A block's scores
stay in raw units, q·k, masked as ever: the forward tracks the running
maximum ``m`` in those units and computes ``p = exp2((s - m) · c)``, ``c =
scale · log2 e`` a constant of the trace, where ``exp(s · scale - m)`` costs a
multiplication more an element before the exponential and another inside
it; the backward computes ``p = exp2(s · c - lse · log2 e)`` and ``ds = p ·
(dp - delta)`` without ``scale``, which multiplies dk and dq once, float32,
as they are written out. The log-sum-exp leaves the forward as it always
did, in natural-log units of the scaled scores (``m · scale + log l``).
The forward's ``m`` and ``l`` are ``[block_q, 128]`` scratch (what
``[block_q, 1]`` float32 occupies in VMEM anyway): ``m`` the same in every
lane; ``l`` a lane's share of the row's sum, the block's probabilities added
register onto register, summed across lanes once, when the q block is
written out. The query side's segment ids (the second rule's three marks)
ride ``[rows, L, 128]`` (``3 · 128``), a position's value in every lane
(what ``_STAT_W`` lanes pad to in HBM, and in the DMA, anyway). So every
block-wide use of a row's value is whole registers repeated
(``pltpu.repeat``) or a lane slice, for values up to 256 lanes wide, and
never a broadcast out of one lane, which costs a pass through the XLU and a
masked store a register. The forward's schedule for a 512 x 512 block fell
from 1,962 bundles, of which no unit filled half, to 1,105, 85% of them
filled by the MXU (64/64; 2,452 to 1,639 at 192/128; 1,981 to 1,099 under
the second rule). The backward is bound by the MXU, five products a block,
each half-filling a 128-wide array at head size 64 (83 to 90% full): the
fold took 5% of its bundles at 64/64 and 15% under the second rule, and
what is left is the formulation's. ``m`` starts at ``_M_NONE``, above the
masked scores: a row that sees no key (padding under the second rule, a q
block the map gives nothing) has probabilities of exactly 0, an output of
0 and no gradient, at any scale.

**One backward kernel.** A block's scores, masks, exponentials, ``dp`` and
``ds`` are computed once and feed all three gradients (PR 27; two kernels,
q-major for dq and kv-major for dk/dv, each recomputed them). The kernel is
kv-major: dk and dv of the kv block accumulate in block-sized scratch over
the q blocks that need it, and dq accumulates in a float32 scratch that
holds the **whole row** ``[L_q, d]`` of one (batch, head) in VMEM across the
row's grid steps, zeroed at the first and written out at the last.
For a fixed q block the kv blocks arrive in increasing order, so the float32
sum is the q-major kernel's and dq, dk, dv are bit-identical to the two
kernels' (read on the chip at 64/64 and 192/128, PERF.md §6). The call asks
Mosaic for the row's VMEM on top of the default 16 MiB
(:func:`_bwd_vmem_limit`: 4 MiB at 4096 x 64, 16 MiB at 8192 x 192), up to 96 of a
v5e's 128 MiB: **the largest row is 81,920 positions at a head size up to
128 and 40,960 up to 256**; a longer one is refused at trace time by a
``ValueError`` that names the limit (a row that long is sharded over chips:
:mod:`~tensorflowonspark_tpu.parallel.ring_attention`).

**The block map and the work list.** The kernels visit only the blocks the
job needs. From the segment ids (in the jitted step, a few thousand integers
in XLA) comes, per batch row, each block's [smallest, largest] id
(:mod:`~tensorflowonspark_tpu.ops.flash_blocks` holds the rule), from those
the needed (q block, kv block) pairs, and from those, per batch row and per
kernel, **one flat list of the needed blocks** in outer-major order with the
inner blocks ascending (q-major for the forward, kv-major for the backward;
:func:`flash_blocks.work_list`): one packed int32 an item, naming its two
blocks and whether it is the first of its outer block (zero the block
accumulators), the last (write them out) and whether to compute at all (an
outer block that needs nothing keeps one item that computes nothing, so it
is still written). The list rides into the kernel as its scalar-prefetch
operand and the grid is ``(batch·heads, steps)``: every index map reads the
item of ``(b // heads, t)``. ``steps`` is the batch's longest list (a traced
grid bound; the interpreter is given the shape's bound,
:func:`flash_blocks.work_bound`: the causal triangle, or the square without
``causal``), and a row with a shorter list parks on its last item: a parked
step names the blocks already resident, so Pallas copies nothing, and
computes nothing. A skipped block would have contributed exactly 0
(``exp2((_NEG_BIG - m) · c)``), so outputs and gradients are bit-identical to
the dense grid's. Without segment ids the list is the causal triangle (or
everything). A computed block is masked as before, causal and fence both:
choosing a body by what a block needs (no mask below the diagonal inside
one document) was built and read on the chip in PR 25, where it ran 2%
slower than masking always and cost three times the tracing (PERF.md §6).

**What the lists cost.** The lists of all batch rows of a call sit in SMEM
(1 MiB on a v5e), four bytes an item, the shape's bound a row: 36 items at
4096 with 512 x 512 blocks, 136 at 8192, 528 at 16,384 and 12,880 (50 KiB)
at the 81,920 positions the backward's VMEM limit allows, of which 19 batch
rows fit; without ``causal`` the bound is the square (25,600 there, 9 rows).
A call whose lists would take more than 960 KiB, or with more than 16,384
blocks along an axis, is refused at trace time by a ``ValueError`` that
names the limit (:func:`_work`).

**What a recomputed layer keeps.** The call hands its backward its operands,
its output ``o`` and the log-sum-exp, one float32 a position and head (the
kernels read and write the statistic ``_STAT_W`` lanes wide, which pad to
128 in HBM: it is narrowed where it leaves the forward and widened again,
times log2 e, where the backward, as ``delta``, is fed). Both are passed through
``jax.ad_checkpoint.checkpoint_name`` (:data:`KEPT_O`, :data:`KEPT_LSE`) and
are the call's only results, so a model that recomputes its layers under
:data:`REMAT_POLICY` rebuilds q, k and v in the recomputed pass and does not
run the forward kernel a second time; without such a policy the names are
identities. ``o`` is named with its heads merged, ``[batch, L, heads·d_v]``,
as a model's output projection reads it: 2 · heads · d_v bytes a token in
bfloat16 (the kernel's own ``[batch·heads, L, d_v]`` pads 64 lanes to 128,
twice the bytes, and read 1.3 ms a layer slower on the chip, ``PERF.md`` §6
PR 32). The way back into the kernel's layout folds against the model's own
merge in the forward pass and is one transposition in the recomputed one.
:data:`REMAT_POLICY` also keeps two names that the models give, not this
module: :data:`KEPT_PROJECTED`, the results of the attention sub-layer's
projections of its input (q, k and v as the projections return them, before
any head norm, whose backward reads them: 2 · (heads + 2 · kv heads) · d
bytes a token in bfloat16; under latent attention the two latents, 2 · (q
rank + kv rank + rotary width), the up-projections running again), and
:data:`KEPT_ATTENDED`, the sub-layer's result after its output projection (2
· hidden bytes a token). With them the recomputed pass multiplies by none of
those matrices, on any attention path: it rebuilds q, k and v for the
backward kernel by norms, rotary and transposes alone. What that costs is
memory: the dense LM at d_model 1024 keeps 4 · 2 · 1024 bytes a token and
layer more (3.2 GB over 24 layers at 4 rows of 4096), and a job that fitted
its chip by less fails at compile time with XLA's out-of-memory message.

**Key/value groups.** ``k`` and ``v`` may have fewer heads than ``q``: query
head ``h`` of ``G = heads / kv heads`` a group reads key/value head ``h //
G`` (grouped-query attention). Nothing is repeated in HBM. The forward
kernel's grid is the same ``(batch·heads, steps)`` and its key and value
index maps read block ``b // G``. The backward kernel's grid becomes
``(batch·kv heads, G, steps)``: a key/value head's ``dk`` and ``dv``
accumulate, float32, over its ``G`` query heads as well as over the q blocks,
so they too are kept as whole rows ``[L_k, d]`` in VMEM (zeroed at the
group's first step, written at its last), beside the dq row of the query
head at hand (:func:`_bwd_vmem_limit`: 40 MiB in all at 8192 x 128). With one
head a group the programs are the ones described above, operation for
operation.

**A second rule.** ``rule="block_diffusion"`` masks and skips by the
block-diffusion rule (:mod:`~tensorflowonspark_tpu.ops.flash_blocks`: a row
holds a clean and a noised copy of every document; ``labels`` beside the
segment ids say which block and which copy a position is). The marks of a
position (four int32: :func:`flash_blocks.bd_marks`) ride where the segment
ids ride (the query side's three 128 lanes each), the mask is two
comparisons and an equality on a block's marks,
never an ``[L, L]`` array, and the work lists hold the blocks the rule needs,
on either side of the diagonal (their stride is the square). The kernels are
named ``flash_fwd_bd`` and ``flash_bwd_dkv_bd``.

**A third rule.** ``rule="window"`` with a static ``window`` is the causal
rule within a window: a query sees the keys of its own document at most
``window - 1`` positions before it, and itself. The mask is the causal one
with a second comparison of the same two position arrays, the work lists
hold the blocks of the band that the ids need (a q block's first needed kv
block moves with it; :func:`flash_blocks.window_blocks`), and their stride is
the band's length, not the triangle's. Inside this module the rule travels as
the pair ``("window", window)``, one static value where the other rules are a
string. The kernels are named ``flash_fwd_win`` and ``flash_bwd_dkv_win``.

This is the single-device analogue of
:mod:`tensorflowonspark_tpu.parallel.ring_attention` (same math, blocks
streamed from local HBM instead of rotated over ICI). ``interpret=True`` runs
the kernels on CPU for tests.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowonspark_tpu.ops import flash_blocks
from tensorflowonspark_tpu.ops.flash_blocks import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q

_NEG_BIG = -0.7 * float(jnp.finfo(jnp.float32).max)

#: the running maximum of a query row that has seen no key yet, in the scores'
#: raw units: far below any score and above the masked scores' ``_NEG_BIG``, so
#: a block that is all fence to a row gives it probabilities of exactly 0
#: (``exp2((_NEG_BIG - _M_NONE) · c)``; no ``inf - inf`` whatever the scale),
#: and a row that sees nothing at all an output of 0 and no gradient
_M_NONE = 0.5 * _NEG_BIG

_LOG2_E = math.log2(math.e)

#: lanes of a vector register. The forward's row statistics and the query
#: side's ids are kept this wide, every lane of a row the same value, so a
#: block-wide use is whole registers repeated and never a broadcast out of one
#: lane through the XLU
_LANES = 128

#: the names (``jax.ad_checkpoint.checkpoint_name``) of what a call hands its
#: backward besides its operands: the output and the log-sum-exp, one float32
#: a position and head. They are identities until a policy asks for them
KEPT_O = "tos.flash_o"
KEPT_LSE = "tos.flash_lse"

#: the names the models give their attention sub-layer's products where they
#: compute them: what the sub-layer's input is projected to (q, k and v as the
#: projections return them; under latent attention the two latents), and the
#: sub-layer's result after its output projection
KEPT_PROJECTED = "tos.attn_projected"
KEPT_ATTENDED = "tos.attn_result"

#: the policy of a model that recomputes its layers (``nn.remat``): a layer
#: keeps its input, as always, and these four, so the recomputed pass runs
#: neither the forward kernel nor any of the attention's products whose
#: results are named: it starts, in effect, at the mid-layer residual
REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(KEPT_O, KEPT_LSE, KEPT_PROJECTED, KEPT_ATTENDED)

#: row-statistics (lse/delta) are stored [BH, L, _STAT_W]: TPU block shapes
#: need a tileable trailing dim, and a trailing dim equal to the full array
#: dim is allowed, so 8 lanes is the cheapest legal width
_STAT_W = 8


def _row(b, heads):
    """The batch row of grid index ``b`` over batch·heads (``b`` ≥ 0, so the
    truncating division is the floor).

    Scalar code here and in :func:`_outer`, :func:`_inner` and :func:`_flag`
    is written in ``lax`` primitives: it is traced into every index map of
    every call (72 a step in a 24-layer model that recomputes), and a ``jnp``
    wrapper or a floor division costs milliseconds of lowering each time
    (PERF.md §6, PR 25)."""
    return jax.lax.div(b, jnp.int32(heads))


def _outer(item):
    """The outer block of a work-list item."""
    return jax.lax.shift_right_logical(item, jnp.int32(flash_blocks.ITEM_OUTER_SHIFT))


def _inner(item):
    """The inner block of a work-list item."""
    return jax.lax.bitwise_and(
        jax.lax.shift_right_logical(item, jnp.int32(flash_blocks.ITEM_INNER_SHIFT)),
        jnp.int32(flash_blocks.ITEM_BLOCKS_MOST - 1))


def _flag(item, bit):
    return jax.lax.bitwise_and(item, jnp.int32(bit)) != 0


def _here(items_ref, heads, steps, axis=1):
    """This grid step's place: ``(outer, inner, item)``, from entry ``t`` of
    the work list of ``b``'s batch row (``steps`` entries a row; ``heads``
    is how many values of ``b`` a batch row has, ``axis`` the grid axis that
    walks the list)."""
    item = items_ref[_row(pl.program_id(0), heads) * steps + pl.program_id(axis)]
    return _outer(item), _inner(item), item


def _window(rule):
    """The window of the third rule (``rule`` is ``("window", window)``), or
    None under the other two."""
    return rule[1] if isinstance(rule, tuple) else None


def _causal_mask(s, iq, ik, block_q, block_k, window=None):
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if window is not None:
        behind = q_pos - k_pos
        return jnp.where((behind >= 0) & (behind < window), s, _NEG_BIG)
    return jnp.where(q_pos >= k_pos, s, _NEG_BIG)


def _segment_mask(s, sq_ref, sk_ref):
    """Packed-sequence fence: scores survive only where the query's segment
    id equals the key's. ``sq_ref`` blocks are [block_q, _LANES], a row's id
    in every lane (as the forward's row statistics); ``sk_ref`` blocks come
    from the pre-transposed [rows, _STAT_W, L] layout so the kernel reads a
    [1, block_k] row directly — no in-kernel transpose."""
    seg_q = _lanes(sq_ref[0], s.shape[1])  # [bq, bk]
    seg_k = sk_ref[0][:1, :]  # [1, bk]
    return jnp.where(seg_q == seg_k, s, _NEG_BIG)


def _block_diffusion_mask(s, sq_ref, sk_ref):
    """The block-diffusion rule on a block's marks
    (:func:`flash_blocks.bd_marks`): the query side holds ``lo``, ``hi`` and
    ``own``, ``_LANES`` lanes each, the key side holds ``key``."""
    marks, block_k = sq_ref[0], s.shape[1]
    lo, hi, own = (_lanes(marks[:, i * _LANES:(i + 1) * _LANES], block_k) for i in range(3))  # [bq, bk]
    key = sk_ref[0][:1, :]  # [1, bk]
    return jnp.where(((key >= lo) & (key <= hi)) | (key == own), s, _NEG_BIG)


def _scores(q_ref, k_ref, sq_ref, sk_ref, iq, ik, causal, block_q, block_k, rule="causal"):
    """A block's masked scores in raw units, q·k: ``scale`` never touches a
    block-sized array (the kernels fold it into the exponent)."""
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if rule == "block_diffusion":
        return _block_diffusion_mask(s, sq_ref, sk_ref)
    if causal:
        s = _causal_mask(s, iq, ik, block_q, block_k, _window(rule))
    if sq_ref is not None:
        s = _segment_mask(s, sq_ref, sk_ref)
    return s


def _lanes(stat, width):
    """``[rows, _LANES]`` with a row's value in every lane as ``[rows,
    width]``: whole registers repeated, or a lane slice."""
    if width > _LANES:
        stat = pltpu.repeat(stat, -(-width // _LANES), axis=1)
    return stat if stat.shape[1] == width else stat[:, :width]


def _lane_sums(p):
    """``[rows, _LANES]`` whose lanes sum to ``p``'s rows' sums: ``p``'s
    registers added onto one another, no reduction across lanes (a block
    that is not whole registers wide puts the row's sum in lane 0)."""
    rows, width = p.shape
    if width % _LANES:
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
        return jnp.where(lane == 0, jnp.sum(p, axis=1, keepdims=True), 0.0)
    return sum(p[:, at:at + _LANES] for at in range(0, width, _LANES))


def _fwd_kernel(items_ref, *refs, scale, causal, segmented, block_q, block_k, heads, steps, rule="causal"):
    """The online softmax in the scores' raw units (the module's text):
    ``m`` the running maximum of q·k, the same in every lane; ``l`` a lane's
    share of the row's sum, summed over the lanes once, when the q block is
    written out."""
    if segmented:
        q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref, acc, m, l = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l = refs
        sq_ref = sk_ref = None
    iq, ik, item = _here(items_ref, heads, steps)
    c = scale * _LOG2_E

    @pl.when(_flag(item, flash_blocks.ITEM_FIRST))
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, _M_NONE)
        l[:] = jnp.zeros_like(l)

    @pl.when(_flag(item, flash_blocks.ITEM_COMPUTE))
    def _block():
        s = _scores(q_ref, k_ref, sq_ref, sk_ref, iq, ik, causal, block_q, block_k, rule)
        m_old = m[:]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp2((m_old - m_new) * c)
        p = jnp.exp2((s - _lanes(m_new, block_k)) * c)
        l[:] = l[:] * corr + _lane_sums(p)
        acc[:] = acc[:] * _lanes(corr, acc.shape[1]) + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m[:] = m_new

    @pl.when(_flag(item, flash_blocks.ITEM_LAST))
    def _finish():
        # a q block that no kv block served still writes finite rows: zeros
        denom = jnp.maximum(jnp.sum(l[:], axis=1, keepdims=True), 1e-30)
        o_ref[0] = (acc[:] / denom).astype(o_ref.dtype)
        # natural-log units of the scaled scores, as the backward and a
        # recomputed layer's policy keep it; finite whatever the scale
        lse = jnp.maximum(m[:, :1] * scale, _M_NONE) + jnp.log(denom)
        lse_ref[0] = jnp.broadcast_to(lse, (l.shape[0], _STAT_W))


def _bwd_refs(refs, segmented):
    """A backward kernel's references, ``(inputs, outputs, scratch)``, the
    ids' two as None where the call has none."""
    if segmented:
        return refs[:8], refs[8:11], refs[11:]
    return refs[:3] + (None, None) + refs[3:6], refs[6:9], refs[9:]


def _bwd_pair(inputs, scratch, iq, ik, at_k, scale, causal, block_q, block_k, rule):
    """The whole backward of one (kv block, q block) pair, added into the
    three accumulators: dq's rows of q block ``iq`` of the whole-row scratch,
    dk's and dv's at ``at_k`` of theirs (all of a block-sized scratch, or the
    kv block's rows of a whole-row one)."""
    q_ref, k_ref, v_ref, sq_ref, sk_ref, do_ref, lse_ref, delta_ref = inputs
    dq_acc, dk_acc, dv_acc = scratch
    s = _scores(q_ref, k_ref, sq_ref, sk_ref, iq, ik, causal, block_q, block_k, rule)
    # the forward's probabilities again, ``scale`` folded into the exponent
    # likewise: lse_ref holds the log-sum-exp times log2 e
    p = jnp.exp2(s * (scale * _LOG2_E) - lse_ref[0][:, :1])  # [bq, bk]
    dv_acc[at_k] += jax.lax.dot_general(
        p.astype(do_ref.dtype), do_ref[0],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(
        do_ref[0], v_ref[0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # d(scaled scores): ``scale`` multiplies dk and dq once, as they are written out
    ds = (p * (dp - delta_ref[0][:, :1])).astype(q_ref.dtype)  # [bq, bk]
    dk_acc[at_k] += jax.lax.dot_general(
        ds, q_ref[0],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    rows = pl.ds(pl.multiple_of(iq * block_q, block_q), block_q)
    dq_acc[rows, :] += jax.lax.dot_general(
        ds, k_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _bwd_kernel(items_ref, *refs, scale, causal, segmented, block_q, block_k, heads, steps, rule="causal"):
    """The whole backward of one (kv block, q block) pair: kv-major (the
    work list's outer block is the kv block, the q blocks that need it
    follow one another), so dk and dv accumulate in block-sized scratch and
    leave with the kv block's last item, while dq accumulates, float32, in
    the scratch ``dq_acc`` that holds the whole ``[L_q, d]`` row of this
    (batch, head) across the list. For a fixed q block the contributions
    arrive in increasing kv block, the order a q-major pass would sum them
    in."""
    inputs, (dq_ref, dk_ref, dv_ref), scratch = _bwd_refs(refs, segmented)
    dq_acc, dk_acc, dv_acc = scratch
    ik, iq, item = _here(items_ref, heads, steps)  # note: kv outer, q inner
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init_row():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_flag(item, flash_blocks.ITEM_FIRST))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_flag(item, flash_blocks.ITEM_COMPUTE))
    def _block():
        _bwd_pair(inputs, scratch, iq, ik, slice(None), scale, causal, block_q, block_k, rule)

    @pl.when(_flag(item, flash_blocks.ITEM_LAST))
    def _finish():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish_row():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_kernel_grouped(items_ref, *refs, scale, causal, segmented, block_q, block_k, heads, steps, rule="causal"):
    """:func:`_bwd_kernel` where ``G`` query heads share a key/value head, on
    the grid ``(batch·kv heads, G, steps)`` (``heads`` counts the kv heads of
    a batch row): the same pair's work, with dk and dv summed into whole-row
    scratch ``[L_k, d]`` over the group's query heads and their q blocks, and
    the dq row of the query head at hand zeroed and written once a head."""
    inputs, (dq_ref, dk_ref, dv_ref), scratch = _bwd_refs(refs, segmented)
    dq_acc, dk_acc, dv_acc = scratch
    ik, iq, item = _here(items_ref, heads, steps, axis=2)  # kv outer, q inner
    g, t = pl.program_id(1), pl.program_id(2)
    last = t == pl.num_programs(2) - 1

    @pl.when(t == 0)
    def _init_row():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when((t == 0) & (g == 0))
    def _init_group():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_flag(item, flash_blocks.ITEM_COMPUTE))
    def _block():
        at_k = (pl.ds(pl.multiple_of(ik * block_k, block_k), block_k), slice(None))
        _bwd_pair(inputs, scratch, iq, ik, at_k, scale, causal, block_q, block_k, rule)

    @pl.when(last)
    def _finish_row():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)

    @pl.when(last & (g == pl.num_programs(1) - 1))
    def _finish_group():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _block_map(seg, n_q, n_k, block_q, block_k, causal, rule="causal"):
    """The work lists of ``seg`` (``int32 [rows, L]``, or None: one row of
    one segment): ``(forward, backward)``, each ``(items, longest)``: a flat
    int32 table of :func:`flash_blocks.work_list` items, :func:`_steps`
    entries a row, q-major for the forward kernel and kv-major for the
    backward, and the length of the batch's longest list, the kernel's
    traced grid bound.

    Jitted on its own so that a model's layers, which all call it on the same
    shapes, trace and lower its few dozen integer operations once and not
    three times a layer."""
    if rule == "block_diffusion":  # seg holds the four marks of every position, [rows, 4, L]
        needed = flash_blocks.bd_blocks_needed(
            flash_blocks.bd_bounds(tuple(seg[:, i] for i in range(4)), block_q, block_k, xp=jnp))
    else:
        if seg is None:
            zq, zk = jnp.zeros((1, n_q), jnp.int32), jnp.zeros((1, n_k), jnp.int32)
            bounds = (zq, zq, zk, zk)
        else:
            bounds = flash_blocks.block_bounds(seg, block_q, block_k, xp=jnp)
        if _window(rule) is None:
            needed = flash_blocks.blocks_needed(bounds, block_q, block_k, causal, xp=jnp)
        else:
            needed = flash_blocks.window_blocks_needed(bounds, block_q, block_k, _window(rule), xp=jnp)
    fwd_steps, bwd_steps = _steps(n_q, n_k, block_q, block_k, causal, rule)
    forward, fwd_lengths = flash_blocks.work_list(needed, fwd_steps, xp=jnp)
    backward, bwd_lengths = flash_blocks.work_list(needed.swapaxes(1, 2), bwd_steps, xp=jnp)
    return (forward.reshape(-1), fwd_lengths.max()), (backward.reshape(-1), bwd_lengths.max())


#: what the work lists of a call (every batch row's) may take of SMEM, where
#: scalar-prefetch operands live
_SMEM_MOST = 960 * 2 ** 10


def _steps(n_q, n_k, block_q, block_k, causal, rule="causal"):
    """``(forward, backward)`` lengths of the accumulating grid axis: the
    longest work list the shape allows (:func:`flash_blocks.work_bound`: the
    window's band, the causal triangle, or the square), Python integers."""
    dense = flash_blocks.dense_blocks(n_q, n_k, block_q, block_k, causal, _window(rule))
    return flash_blocks.work_bound(dense), flash_blocks.work_bound(dense.T)


def _work(seg, rows, n_q, n_k, block_q, block_k, causal, backward, rule="causal"):
    """``(steps, items, longest)`` of one kernel's call: the row stride of
    its table of work lists (the shape's bound), the table, and the batch's
    longest list. Refuses a call whose lists would not fit: the lists of all
    batch rows ride in SMEM, and an item names a block in 14 bits."""
    steps = _steps(n_q, n_k, block_q, block_k, causal, rule)[backward]
    if max(n_q, n_k) > flash_blocks.ITEM_BLOCKS_MOST or 4 * rows * steps > _SMEM_MOST:
        raise ValueError(
            "flash attention walks a list of the blocks it needs, kept in SMEM: {} rows x {} blocks ({} x {} "
            "a row) take {:.0f} KiB of the {:.0f} it may (and an axis at most {} blocks); use larger blocks, "
            "or shard a row this long over chips (parallel.ring_attention)".format(
                rows, steps, n_q, n_k, 4 * rows * steps / 2 ** 10, _SMEM_MOST / 2 ** 10,
                flash_blocks.ITEM_BLOCKS_MOST))
    return (steps,) + _block_map(seg, n_q, n_k, block_q, block_k, causal, rule)[backward]


class _Specs:
    """BlockSpecs of one kernel's operands on the grid ``(batch·heads,
    steps)``: ``outer`` blocks follow the outer block of the step's
    work-list item, ``inner`` blocks its inner block (the kernel's
    scalar-prefetch table). Segment ids are per batch row, not per head:
    ``ids=True`` indexes them by ``b // heads``."""

    def __init__(self, heads, steps, group=1):
        self.heads, self.steps, self.group = heads, steps, group

    def _index(self, inner, ids, transposed, shared=False):
        heads, steps, group = self.heads, self.steps, self.group

        def index_map(b, t, items):
            row = _row(b, heads)
            at = (_inner if inner else _outer)(items[row * steps + t])
            first = row if ids else b
            if shared and group > 1:  # the key/value head of query head b
                first = jax.lax.div(b, jnp.int32(group))
            return (first, 0, at) if transposed else (first, at, 0)

        return index_map

    def rows(self, block_rows, width, inner=False, ids=False, shared=False):
        """Blocks of ``block_rows`` rows of a [·, L, width] operand;
        ``shared``: a key/value operand, one head for ``group`` query heads."""
        return pl.BlockSpec((1, block_rows, width), self._index(inner, ids, False, shared))

    def seg_k(self, block_k, inner=False):
        """Blocks of the transposed [rows, _STAT_W, L] key-segment layout."""
        return pl.BlockSpec((1, _STAT_W, block_k), self._index(inner, True, True))

    @staticmethod
    def whole_row(length, width):
        """All ``length`` rows of one (batch, head): the block stays where it
        is along the list and moves once per ``b``."""
        return pl.BlockSpec((1, length, width), lambda b, t, items: (b, 0, 0))


class _GroupSpecs:
    """:class:`_Specs` for the grouped backward's grid ``(batch·kv heads, G,
    steps)``, kv-major: ``kv_heads`` key/value heads a batch row, ``group``
    query heads each. Query-side operands (``[batch·heads, L, ·]``) follow the
    item's inner block of query head ``b * G + g``; a key/value head's
    operands its outer block; dk and dv are whole rows that move once a
    key/value head, dq a whole row that moves once a query head."""

    def __init__(self, kv_heads, steps, group):
        self.kv_heads, self.steps, self.group = kv_heads, steps, group

    def _item(self, b, t, items):
        return items[_row(b, self.kv_heads) * self.steps + t]

    def q_rows(self, block_q, width):
        return pl.BlockSpec(
            (1, block_q, width),
            lambda b, g, t, items: (b * jnp.int32(self.group) + g, _inner(self._item(b, t, items)), 0))

    def kv_rows(self, block_k, width):
        return pl.BlockSpec((1, block_k, width), lambda b, g, t, items: (b, _outer(self._item(b, t, items)), 0))

    def ids_q(self, block_q, width):
        return pl.BlockSpec(
            (1, block_q, width),
            lambda b, g, t, items: (_row(b, self.kv_heads), _inner(self._item(b, t, items)), 0))

    def ids_k(self, block_k):
        return pl.BlockSpec(
            (1, _STAT_W, block_k),
            lambda b, g, t, items: (_row(b, self.kv_heads), 0, _outer(self._item(b, t, items))))

    def q_whole_row(self, length, width):
        return pl.BlockSpec((1, length, width), lambda b, g, t, items: (b * jnp.int32(self.group) + g, 0, 0))

    @staticmethod
    def kv_whole_row(length, width):
        return pl.BlockSpec((1, length, width), lambda b, g, t, items: (b, 0, 0))


def _seg_inputs(seg, rule="causal"):
    """Segment-id operands for the kernels, one set per batch row: query ids
    ``[rows, L, _LANES]``, a position's id in every lane (what ``[rows, L,
    _STAT_W]`` pads to in HBM anyway), key ids pre-transposed to ``[rows,
    _STAT_W, L]`` so a kv block is a directly-loadable row vector. Under the
    block-diffusion rule ``seg`` is the marks ``[rows, 4, L]``: the query
    side ``[rows, L, 3 · _LANES]``, ``lo``, ``hi`` and ``own`` ``_LANES``
    lanes each, ``key`` on the key side."""
    if rule == "block_diffusion":
        rows, _, seq = seg.shape
        lanes = jnp.repeat(seg[:, :3].transpose(0, 2, 1), _LANES, axis=2)
        return lanes, jnp.broadcast_to(seg[:, 3:4], (rows, _STAT_W, seq))
    rows, seq = seg.shape
    seg = seg.astype(jnp.int32)
    seg_q = jnp.broadcast_to(seg[:, :, None], (rows, seq, _LANES))
    seg_k = jnp.broadcast_to(seg[:, None, :], (rows, _STAT_W, seq))
    return seg_q, seg_k


def _geometry(q, k, seg, block_q, block_k):
    """``(block_q, block_k, n_q, n_k, heads)`` of a call on ``[BH, L, D]``
    operands whose ids, if any, are ``[B, L]``."""
    block_q = flash_blocks.pick_block(q.shape[1], block_q)
    block_k = flash_blocks.pick_block(k.shape[1], block_k)
    heads = q.shape[0] // (1 if seg is None else seg.shape[0])
    return block_q, block_k, q.shape[1] // block_q, k.shape[1] // block_k, heads


def _group(q, k):
    """Query heads a key/value head: 1 unless ``k`` has fewer heads than ``q``."""
    if q.shape[0] % k.shape[0]:
        raise ValueError("flash attention: {} query heads do not divide into {} key/value heads".format(
            q.shape[0], k.shape[0]))
    return q.shape[0] // k.shape[0]


def _kernel_name(which, segmented, rule="causal"):
    """Stable kernel names (``flash_fwd``, ``flash_bwd_dkv``; ``_seg`` when
    the segment fence is compiled in): the Mosaic custom call carries the
    name into the compiled HLO and the profiler trace, where ``chip_smoke.py``
    and trace reductions look for it. The one backward kernel is the
    kv-major pass that always bore ``flash_bwd_dkv``, now emitting dq too,
    and keeps that name: the benchmark's readers sum the flash kernels they
    find by name, and a new name would drop the whole backward from them."""
    if rule == "block_diffusion":
        return "flash_{}_bd".format(which)
    if _window(rule) is not None:
        return "flash_{}_win".format(which)
    return "flash_{}{}".format(which, "_seg" if segmented else "")


#: what a kernel may use of VMEM unless it says otherwise (Mosaic's scoped
#: default on a v5e), and what the backward may ask for of the chip's 128 MiB
_VMEM_DEFAULT = 16 * 2 ** 20
_VMEM_MOST = 96 * 2 ** 20


def _bwd_vmem_limit(length, width, dtype, kv_rows=()):
    """The VMEM limit the backward sets: the default plus its dq row (the
    float32 accumulator and the output block's two buffers, lanes padded to
    128) and, where query heads share key/value heads, the dk and dv rows
    likewise (``kv_rows``: their ``(length, width)``). A row that would take
    it past :data:`_VMEM_MOST` is refused."""
    row = 0
    for length, width in ((length, width),) + tuple(kv_rows):
        row += length * (-(-width // 128) * 128) * (4 + 2 * jnp.dtype(dtype).itemsize)
    if _VMEM_DEFAULT + row > _VMEM_MOST:
        raise ValueError(
            "flash attention's backward keeps the dq of a whole row in VMEM: {} x {} takes {:.1f} MiB "
            "of the {:.1f} it may; shard a row this long over chips (parallel.ring_attention)".format(
                length, width, row / 2 ** 20, (_VMEM_MOST - _VMEM_DEFAULT) / 2 ** 20))
    return _VMEM_DEFAULT + row


def _compiler_params(interpret, row_limit=None, dims=2):
    """The batch·heads grid dim runs in any order; the list's dim carries
    the block accumulators (and the backward's dq row), so it is
    'arbitrary', as is the grouped backward's dim over a group's query heads
    (``dims`` 3). ``row_limit`` is the backward's VMEM limit."""
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) + ("arbitrary",) * (dims - 1), vmem_limit_bytes=row_limit)


def _call(kernel, which, work, bh, in_specs, out_specs, out_shape, scratch_shapes,
          operands, segmented, interpret, row_limit=None, rule="causal", group=None):
    """One kernel over the grid ``(bh, the batch's longest list)``, or
    ``(bh, group, list)`` for the grouped backward. The interpreter is given
    the shape's bound instead (a static grid; every row then parks for the
    rest), and nothing else differs."""
    steps, items, longest = work
    grid = (bh,) + (() if group is None else (group,)) + (steps if interpret else longest,)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes,
        ),
        out_shape=out_shape,
        compiler_params=_compiler_params(interpret, row_limit, len(grid)),
        interpret=interpret,
        name=_kernel_name(which, segmented, rule),
    )(items, *operands)


def _flash_fwd(q, k, v, seg, scale, causal, block_q, block_k, interpret, rule="causal"):
    bh, l_q, d = q.shape
    d_v = v.shape[2]  # v and o may be narrower than q and k (latent attention: 192 / 128)
    block_q, block_k, n_q, n_k, heads = _geometry(q, k, seg, block_q, block_k)
    segmented, group = seg is not None, _group(q, k)
    work = _work(seg, bh // heads, n_q, n_k, block_q, block_k, causal, False, rule)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, segmented=segmented,
        block_q=block_q, block_k=block_k, heads=heads, steps=work[0], rule=rule,
    )
    at = _Specs(heads, work[0], group)
    in_specs = [at.rows(block_q, d), at.rows(block_k, d, inner=True, shared=True),
                at.rows(block_k, d_v, inner=True, shared=True)]
    operands = [q, k, v]
    if segmented:
        operands += _seg_inputs(seg, rule)
        in_specs += [at.rows(block_q, operands[3].shape[2], ids=True), at.seg_k(block_k, inner=True)]
    o, lse = _call(
        kernel, "fwd", work, bh, in_specs,
        out_specs=[at.rows(block_q, d_v), at.rows(block_q, _STAT_W)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, l_q, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, l_q, _STAT_W), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d_v), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        operands=operands, segmented=segmented, interpret=interpret, rule=rule,
    )
    return o, lse


def _flash_bwd(q, k, v, seg, do, o, lse, scale, causal, block_q, block_k, interpret, rule="causal"):
    bh, l_q, d = q.shape
    l_k, d_v = k.shape[1], v.shape[2]
    block_q, block_k, n_q, n_k, heads = _geometry(q, k, seg, block_q, block_k)
    segmented, group = seg is not None, _group(q, k)
    work = _work(seg, bh // heads, n_q, n_k, block_q, block_k, causal, True, rule)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, :, None], (bh, l_q, _STAT_W))
    lse = lse * _LOG2_E  # the kernel's exponent is base 2
    if group > 1:
        return _flash_bwd_grouped(
            q, k, v, seg, do, lse, delta, work, group, heads // group, scale, causal, block_q, block_k, interpret, rule)
    row_limit = _bwd_vmem_limit(l_q, d, q.dtype)
    kernel = functools.partial(
        _bwd_kernel, scale=scale, causal=causal, segmented=segmented,
        block_q=block_q, block_k=block_k, heads=heads, steps=work[0], rule=rule,
    )
    at = _Specs(heads, work[0])  # kv outer, q inner
    in_specs = [at.rows(block_q, d, inner=True), at.rows(block_k, d), at.rows(block_k, d_v)]
    operands = [q, k, v]
    if segmented:
        operands += _seg_inputs(seg, rule)
        in_specs += [at.rows(block_q, operands[3].shape[2], inner=True, ids=True), at.seg_k(block_k)]
    in_specs += [
        at.rows(block_q, d_v, inner=True),
        at.rows(block_q, _STAT_W, inner=True),
        at.rows(block_q, _STAT_W, inner=True),
    ]
    dq, dk, dv = _call(
        kernel, "bwd_dkv", work, bh, in_specs,
        out_specs=[at.whole_row(l_q, d), at.rows(block_k, d), at.rows(block_k, d_v)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, l_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, l_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh, l_k, d_v), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((l_q, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d_v), jnp.float32),
        ],
        operands=operands + [do, lse, delta], segmented=segmented, interpret=interpret,
        row_limit=row_limit, rule=rule,
    )
    return dq, dk, dv


def _flash_bwd_grouped(q, k, v, seg, do, lse, delta, work, group, kv_heads, scale, causal, block_q, block_k,
                       interpret, rule):
    """The backward where ``group`` query heads share each of a batch row's
    ``kv_heads`` key/value heads (the module's text)."""
    (bh, l_q, d), (bkv, l_k, _), d_v = q.shape, k.shape, v.shape[2]
    segmented = seg is not None
    row_limit = _bwd_vmem_limit(l_q, d, q.dtype, kv_rows=((l_k, d), (l_k, d_v)))
    kernel = functools.partial(
        _bwd_kernel_grouped, scale=scale, causal=causal, segmented=segmented,
        block_q=block_q, block_k=block_k, heads=kv_heads, steps=work[0], rule=rule,
    )
    at = _GroupSpecs(kv_heads, work[0], group)
    in_specs = [at.q_rows(block_q, d), at.kv_rows(block_k, d), at.kv_rows(block_k, d_v)]
    operands = [q, k, v]
    if segmented:
        operands += _seg_inputs(seg, rule)
        in_specs += [at.ids_q(block_q, operands[3].shape[2]), at.ids_k(block_k)]
    in_specs += [at.q_rows(block_q, d_v), at.q_rows(block_q, _STAT_W), at.q_rows(block_q, _STAT_W)]
    return _call(
        kernel, "bwd_dkv", work, bkv, in_specs,
        out_specs=[at.q_whole_row(l_q, d), at.kv_whole_row(l_k, d), at.kv_whole_row(l_k, d_v)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, l_q, d), q.dtype),
            jax.ShapeDtypeStruct((bkv, l_k, d), k.dtype),
            jax.ShapeDtypeStruct((bkv, l_k, d_v), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((l_q, d), jnp.float32),
            pltpu.VMEM((l_k, d), jnp.float32),
            pltpu.VMEM((l_k, d_v), jnp.float32),
        ],
        operands=operands + [do, lse, delta], segmented=segmented, interpret=interpret,
        row_limit=row_limit, rule=rule, group=group,
    )


def _heads_last(o, heads):
    """``[batch·heads, L, d_v]`` → ``[batch, L, heads·d_v]``, the layout in
    which a model merges its heads."""
    batch, length, d_v = o.shape[0] // heads, o.shape[1], o.shape[2]
    return o.reshape(batch, heads, length, d_v).transpose(0, 2, 1, 3).reshape(batch, length, heads * d_v)


def _heads_first(o, heads):
    """:func:`_heads_last`'s inverse."""
    batch, length, d_v = o.shape[0], o.shape[1], o.shape[2] // heads
    return o.reshape(batch, length, heads, d_v).transpose(0, 2, 1, 3).reshape(batch * heads, length, d_v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_attention_bhld(q, k, v, seg, heads, scale, causal, block_q, block_k, interpret, rule="causal"):
    o, _ = _flash_fwd(q, k, v, seg, scale, causal, block_q, block_k, interpret, rule)
    return o


def _flash_attention_fwd(q, k, v, seg, heads, scale, causal, block_q, block_k, interpret, rule="causal"):
    o, lse = _flash_fwd(q, k, v, seg, scale, causal, block_q, block_k, interpret, rule)
    # the named values are the call's only results, out and residuals both, so
    # a recomputed pass that was told to keep them (REMAT_POLICY) has no use
    # for the call. o with its heads merged: d_v 64 pads to 128 lanes in the
    # kernel's layout; lse one float32 a position: _STAT_W lanes pad to 128
    o = _heads_first(checkpoint_name(_heads_last(o, heads), KEPT_O), heads)
    lse = checkpoint_name(lse[:, :, 0], KEPT_LSE)
    return o, (q, k, v, seg, o, lse)


def _flash_attention_bwd(heads, scale, causal, block_q, block_k, interpret, rule, res, do):
    q, k, v, seg, o, lse = res
    lse = jnp.broadcast_to(lse[:, :, None], lse.shape + (_STAT_W,))
    dq, dk, dv = _flash_bwd(
        q, k, v, seg, do, o, lse, scale, causal, block_q, block_k, interpret, rule
    )
    # integer segment ids carry no gradient (None = zero cotangent)
    return dq, dk, dv, None


_flash_attention_bhld.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def flash_attention(
    q, k, v, causal=False, scale=None, segment_ids=None,
    block_q=None, block_k=None, interpret=False, rule="causal", labels=None, window=None,
):
    """Flash attention over ``[batch, heads, seq, head_dim]`` arrays. ``v``
    (and the output) may have a head size of its own: latent attention's
    queries and keys are 192 wide (128 + the rotary 64) against values of 128.
    ``k`` and ``v`` may have fewer heads than ``q``, a divisor of its count:
    query head ``h`` reads key/value head ``h // (heads / kv heads)``.

    ``rule="block_diffusion"`` (static) masks by the block-diffusion rule
    and wants ``segment_ids`` and ``labels`` (``int32 [batch, seq]``: ``2 *
    block + half``, the block counted from the document's start, half 0 the
    clean copy and 1 the noised; :mod:`~tensorflowonspark_tpu.ops.flash_blocks`
    states the rule). Positions play no part in it, so ``causal`` must be off.

    ``rule="window"`` (static) with ``window`` (a static integer, at least 1)
    is ``causal`` within a window: a query sees the keys of its document at
    most ``window - 1`` positions before it, and itself; ``causal`` must be
    on, ``segment_ids`` are optional as under the first rule.

    Drop-in replacement for
    :func:`tensorflowonspark_tpu.parallel.ring_attention.plain_attention`
    with O(L·D) memory. Sequence lengths must divide into the block sizes
    (pad upstream; the transformer pads its own inputs).

    ``segment_ids`` (``int32 [batch, seq]``, 0 = padding) fences packed
    sequences: scores between positions with different ids are masked, so
    pack neighbours never cross-attend (the text plane's block-diagonal
    contract). Ids are shared across heads and carry no gradient. Blocks in
    which no query shares an id with a key are neither fetched nor computed
    (the module's text), whatever the ids; ids that do not decrease along a
    row, as the text plane's, skip the most.

    ``block_q`` / ``block_k`` default to the sizes read on the chip: the
    segmented kernels' own when ``segment_ids`` is given.
    """
    b, h, l_q, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    merge = lambda t: t.reshape(b * t.shape[1], t.shape[2], t.shape[3])  # noqa: E731
    segmented = segment_ids is not None
    if rule not in flash_blocks.RULES:
        raise ValueError("flash attention: unknown rule {!r}; expected one of {}".format(rule, flash_blocks.RULES))
    if rule == "block_diffusion" and (causal or not segmented or labels is None):
        raise ValueError("flash attention: rule 'block_diffusion' takes segment_ids and labels, and causal=False")
    if (rule == "window") != (window is not None) or (rule == "window" and (not causal or int(window) < 1)):
        raise ValueError("flash attention: rule 'window' takes a window of at least 1 and causal=True, no other rule one")
    if block_q is None:
        block_q = flash_blocks.SEGMENTED_BLOCK_Q if segmented else DEFAULT_BLOCK_Q
    if block_k is None:
        block_k = flash_blocks.SEGMENTED_BLOCK_K if segmented else DEFAULT_BLOCK_K
    seg = segment_ids.astype(jnp.int32) if segmented else None
    if rule == "block_diffusion":
        seg = jnp.stack(flash_blocks.bd_marks(seg, labels, xp=jnp), axis=1)  # [batch, 4, seq]
    o = _flash_attention_bhld(
        merge(q), merge(k), merge(v), seg, h, float(scale), bool(causal),
        int(block_q), int(block_k), bool(interpret), ("window", int(window)) if rule == "window" else rule,
    )
    return o.reshape(b, h, l_q, v.shape[3])
