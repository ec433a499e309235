"""The scan of a Mamba-2 layer (state-space duality, arXiv:2405.21060) in its
chunked matrix form, as two pallas TPU kernels with a custom VJP, restarting
at every document boundary of a packed row.

Per head ``h`` of ``H`` (``P`` channels, one scalar decay), along the
positions of a row, with ``B_t`` and ``C_t`` ``[N]`` those of the head's group
(``H / G`` heads a group)::

    h_t = keep_t * exp(Delta_t a) * h_{t-1} + (Delta_t x_t) (x) B_t      [P, N]
    y_t = h_t C_t + D_skip x_t                                           [P]

``keep_t`` is 0 at a document's first position (``segment_ids``) and 1
elsewhere. :mod:`~tensorflowonspark_tpu.ops.selective_scan` has a decay a
channel *and* state and walks the positions on the vector unit; here the decay
is one scalar a head, so inside a chunk of ``Q`` positions the recurrence is
three products on the matrix unit and only the chunks' ``[P, N]`` states are
carried from chunk to chunk. With ``cum_i`` the chunk's running sum of
``Delta a`` up to and including position ``i``, ``S_ij`` true where ``j <= i``
lie in one document, ``L_ij = S_ij exp(cum_i - cum_j)`` and ``xd = Delta x``::

    Y      = ((C B^T) * L) xd  +  v * (C H^T)          v_i = vis_i exp(cum_i)
    H'     = v_last H  +  xd^T (w * B)                 w_j = L_last,j

``H`` the state the chunk starts from, ``vis_i`` 1 where position ``i`` lies
in the document the chunk before ended in (0 in a row's first chunk). ``C
B^T`` is computed once a group and chunk, the rest once a head. The decays,
their running sums, the masks, the state and every product's accumulator are
float32; the products take their operands in ``x``'s dtype.

**What XLA does and what the kernels do.** ``Delta a``, its running sum within
a chunk (``[rows, L, H]`` float32: small), ``Delta x`` and the skip ``D_skip
x`` are XLA's, differentiated by JAX; the kernels (``ssd_scan_fwd``,
``ssd_scan_bwd``) are the custom VJP's two rules and take ``xd``, the running
sums (twice: a head's as a column ``[Q, 1]`` and as a row ``[1, Q]``, so that
no kernel transposes a vector), ``B``, ``C`` and the ids. One grid step is one
chunk of one group: its heads are walked in a static loop, ``C B^T`` and the
document mask shared. The forward writes the state every chunk starts from
(``[rows, H, L / Q, P, N]`` float32, 67 MB a row of 8192 at 32 heads of 64 x
128); the backward walks the chunks from the last to the first with the
states' cotangent carried in VMEM, and computes nothing of the forward again
but the chunk's own masks and ``C H^T``.

**What a recomputed layer keeps.** ``y`` (before the skip) and the chunks'
states pass through ``checkpoint_name`` (:data:`KEPT_SCANNED`,
:data:`KEPT_STATE`): under a policy that saves them the forward kernel runs
once.

``interpret=True`` runs the kernels on the CPU for tests. On a chip ``(H / G)
P`` and ``N`` are multiples of the register's 128 lanes (or there is one
group), and ``Q`` a multiple of 8.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: positions a chunk: the matrix unit's side
DEFAULT_CHUNK = 128

KEPT_SCANNED = "tos.ssd_y"
KEPT_STATE = "tos.ssd_state"

_VMEM_LIMIT = 64 * 2 ** 20
_MASKED = -1e30


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _nt(a, b):
    """``a b^T``."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _tn(a, b):
    """``a^T b``."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _at_last(column):
    """``column[-1]`` of a ``[Q, 1]`` as a ``[1, 1]`` that may scale a whole
    tile: a masked sum over the sublanes, which leaves the value in all of
    them (Mosaic does not broadcast a register's one corner along both axes)."""
    last = jax.lax.broadcasted_iota(jnp.int32, column.shape, 0) == column.shape[0] - 1
    return jnp.sum(jnp.where(last, column, 0.0), axis=0, keepdims=True)


def _chunk_terms(b_ref, c_ref, segc_ref, segr_ref):
    """What a chunk's heads share: ``B``, ``C``, ``C B^T`` ``[Q, Q]``, the
    mask ``S`` and ``tail`` ``[Q, 1]``, the positions of the document the
    chunk ends in."""
    bm, cm = b_ref[0], c_ref[0]
    seg_col = segc_ref[0]
    square = (seg_col.shape[0],) * 2
    not_after = jax.lax.broadcasted_iota(jnp.int32, square, 0) >= jax.lax.broadcasted_iota(jnp.int32, square, 1)
    return bm, cm, _nt(cm, bm), (seg_col == segr_ref[0]) & not_after, seg_col == seg_col[-1:, :]


def _head_terms(cum_col, cum_row, same, tail, vis):
    """``(L [Q, Q], v [Q, 1], w [Q, 1])`` of one head (the module's text)."""
    decay = jnp.exp(jnp.where(same, cum_col - cum_row, _MASKED))
    v = vis * jnp.exp(cum_col)
    w = jnp.where(tail, jnp.exp(cum_col[-1:, :] - cum_col), 0.0)
    return decay, v, w


def _fwd_kernel(xd_ref, cumc_ref, cumr_ref, b_ref, c_ref, segc_ref, segr_ref, vis_ref, y_ref, hb_ref, h_s,
                *, heads, width):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_s[...] = jnp.zeros(h_s.shape, jnp.float32)

    dt = xd_ref.dtype
    bm, cm, cb, same, tail = _chunk_terms(b_ref, c_ref, segc_ref, segr_ref)
    vis, cum_cols, cum_rows = vis_ref[0], cumc_ref[0, 0], cumr_ref[0, 0]
    b32 = bm.astype(jnp.float32)
    for h in range(heads):
        at = slice(h * width, (h + 1) * width)
        decay, v, w = _head_terms(cum_cols[:, h:h + 1], cum_rows[h:h + 1, :], same, tail, vis)
        xd, state = xd_ref[0, :, at], h_s[h]
        hb_ref[0, h, 0] = state
        y = _nn((cb * decay).astype(dt), xd) + v * _nt(cm, state.astype(dt))
        y_ref[0, :, at] = y.astype(y_ref.dtype)
        h_s[h] = _at_last(v) * state + _tn(xd, (w * b32).astype(dt))


def _bwd_kernel(xd_ref, cumc_ref, cumr_ref, b_ref, c_ref, segc_ref, segr_ref, vis_ref, hb_ref, dy_ref,
                dxd_ref, dcumc_ref, dcumr_ref, db_ref, dc_ref, dh_s, *, chunk, heads, width):
    @pl.when(pl.program_id(2) == 0)  # the row's last chunk: nothing comes after it
    def _start():
        dh_s[...] = jnp.zeros(dh_s.shape, jnp.float32)

    dt = xd_ref.dtype
    bm, cm, cb, same, tail = _chunk_terms(b_ref, c_ref, segc_ref, segr_ref)
    vis, cum_cols, cum_rows = vis_ref[0], cumc_ref[0, 0], cumr_ref[0, 0]
    b32 = bm.astype(jnp.float32)
    last = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    dcb = jnp.zeros((chunk, chunk), jnp.float32)
    db = jnp.zeros(bm.shape, jnp.float32)
    dc = jnp.zeros(cm.shape, jnp.float32)
    for h in range(heads):
        at = slice(h * width, (h + 1) * width)
        decay, v, w = _head_terms(cum_cols[:, h:h + 1], cum_rows[h:h + 1, :], same, tail, vis)
        xd, dy, state, dh = xd_ref[0, :, at], dy_ref[0, :, at], hb_ref[0, h, 0], dh_s[h]
        v_last = _at_last(v)
        mixed = cb * decay  # [Q, Q]: the chunk's own map from xd to y
        dmixed = _nt(dy, xd)
        through_decay = dmixed * mixed  # d mixed / d (cum_i - cum_j), entry by entry
        dcb = dcb + dmixed * decay
        dxd_ref[0, :, at] = (_tn(mixed.astype(dt), dy) + w * _nt(bm, dh.astype(dt))).astype(dxd_ref.dtype)
        dy32 = dy.astype(jnp.float32)
        carried = _nt(cm, state.astype(dt))  # C H^T
        seen = (v * dy32).astype(dt)
        dc = dc + _nn(seen, state.astype(dt))
        into_state = _nn(xd, dh.astype(dt))  # [Q, N]
        db = db + w * into_state
        dw = w * jnp.sum(into_state * b32, axis=1, keepdims=True)
        at_last = v_last * jnp.sum(jnp.sum(dh * state, axis=1, keepdims=True), axis=0, keepdims=True) + jnp.sum(
            dw, axis=0, keepdims=True)
        dcumc_ref[0, 0, :, h:h + 1] = (
            v * jnp.sum(dy32 * carried, axis=1, keepdims=True) + jnp.sum(through_decay, axis=1, keepdims=True)
            - dw + jnp.where(last, at_last, 0.0))
        dcumr_ref[0, 0, h:h + 1, :] = -jnp.sum(through_decay, axis=0, keepdims=True)
        dh_s[h] = v_last * dh + _tn(seen, cm)
    dcb = dcb.astype(dt)
    db_ref[0] = (db + _tn(dcb, cm)).astype(db_ref.dtype)
    dc_ref[0] = (dc + _nn(dcb, bm)).astype(dc_ref.dtype)


def _compiler_params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)


def _specs(chunk, per, width, states, at):
    """The block specs both kernels share, ``at(c)`` the chunk a grid step
    holds: ``xd`` / ``y`` rows, the running sums' columns and rows, ``B`` /
    ``C``, the ids' column and row, the chunk's states."""
    return {
        "row": pl.BlockSpec((1, chunk, per * width), lambda r, g, c: (r, at(c), g)),
        "cum_col": pl.BlockSpec((1, 1, chunk, per), lambda r, g, c: (r, g, at(c), 0)),
        "cum_row": pl.BlockSpec((1, 1, per, chunk), lambda r, g, c: (r, g, 0, at(c))),
        "bc": pl.BlockSpec((1, chunk, states), lambda r, g, c: (r, at(c), g)),
        "col": pl.BlockSpec((1, chunk, 1), lambda r, g, c: (r, at(c), 0)),
        "seg_row": pl.BlockSpec((1, 1, chunk), lambda r, g, c: (r, 0, at(c))),
        "state": pl.BlockSpec((1, per, 1, width, states), lambda r, g, c: (r, g, at(c), 0, 0)),
    }


def _layouts(cum, ids, chunk, groups):
    """The running sums a group as columns ``[rows, G, L, H / G]`` and as rows
    ``[rows, G, H / G, L]``; the ids as a column and a row; ``vis`` ``[rows,
    L, 1]``."""
    rows, length, heads = cum.shape
    by_group = cum.reshape(rows, length, groups, heads // groups)
    before = jnp.pad(ids[:, chunk - 1::chunk], ((0, 0), (1, 0)), constant_values=-2)[:, :length // chunk]
    vis = (ids.reshape(rows, length // chunk, chunk) == before[..., None]).astype(jnp.float32)
    return (by_group.transpose(0, 2, 1, 3), by_group.transpose(0, 2, 3, 1), ids[..., None], ids[:, None, :],
            vis.reshape(rows, length, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _scan(xd, cum, b, c, ids, chunk, heads, groups, interpret):
    return _scan_vjp_fwd(xd, cum, b, c, ids, chunk, heads, groups, interpret)[0]


def _scan_vjp_fwd(xd, cum, b, c, ids, chunk, heads, groups, interpret):
    rows, length, _ = xd.shape
    per, width, states = heads // groups, xd.shape[2] // heads, b.shape[2] // groups
    spec = _specs(chunk, per, width, states, lambda c: c)
    cum_col, cum_row, seg_col, seg_row, vis = _layouts(cum, ids, chunk, groups)
    with jax.named_scope("tos.ssd_scan"):
        y, bound = pl.pallas_call(
            functools.partial(_fwd_kernel, heads=per, width=width),
            grid=(rows, groups, length // chunk),
            in_specs=[spec["row"], spec["cum_col"], spec["cum_row"], spec["bc"], spec["bc"], spec["col"],
                      spec["seg_row"], spec["col"]],
            out_specs=[spec["row"], spec["state"]],
            out_shape=[
                jax.ShapeDtypeStruct(xd.shape, xd.dtype),
                jax.ShapeDtypeStruct((rows, heads, length // chunk, width, states), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((per, width, states), jnp.float32)],
            compiler_params=_compiler_params(interpret),
            interpret=interpret,
            name="ssd_scan_fwd",
        )(xd, cum_col, cum_row, b, c, seg_col, seg_row, vis)
        # the call's only results, out and residual both: a recomputed pass that keeps them has no use for the call
        y, bound = checkpoint_name(y, KEPT_SCANNED), checkpoint_name(bound, KEPT_STATE)
    return y, (xd, cum, b, c, ids, bound)


def _scan_vjp_bwd(chunk, heads, groups, interpret, res, dy):
    xd, cum, b, c, ids, bound = res
    rows, length, _ = xd.shape
    per, width, states = heads // groups, xd.shape[2] // heads, b.shape[2] // groups
    chunks = length // chunk
    spec = _specs(chunk, per, width, states, lambda c: chunks - 1 - c)
    cum_col, cum_row, seg_col, seg_row, vis = _layouts(cum, ids, chunk, groups)
    with jax.named_scope("tos.ssd_scan"):
        dxd, dcum_col, dcum_row, db, dc = pl.pallas_call(
            functools.partial(_bwd_kernel, chunk=chunk, heads=per, width=width),
            grid=(rows, groups, chunks),
            in_specs=[spec["row"], spec["cum_col"], spec["cum_row"], spec["bc"], spec["bc"], spec["col"],
                      spec["seg_row"], spec["col"], spec["state"], spec["row"]],
            out_specs=[spec["row"], spec["cum_col"], spec["cum_row"], spec["bc"], spec["bc"]],
            out_shape=[
                jax.ShapeDtypeStruct(xd.shape, xd.dtype),
                jax.ShapeDtypeStruct(cum_col.shape, jnp.float32),
                jax.ShapeDtypeStruct(cum_row.shape, jnp.float32),
                jax.ShapeDtypeStruct(b.shape, b.dtype),
                jax.ShapeDtypeStruct(c.shape, c.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((per, width, states), jnp.float32)],
            compiler_params=_compiler_params(interpret),
            interpret=interpret,
            name="ssd_scan_bwd",
        )(xd, cum_col, cum_row, b, c, seg_col, seg_row, vis, bound, dy)
        dcum = (dcum_col.transpose(0, 2, 1, 3) + dcum_row.transpose(0, 3, 1, 2)).reshape(cum.shape)
    return dxd, dcum, db, dc, None


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def chunks_of(length, chunk=DEFAULT_CHUNK):
    """``(chunk, chunks)`` a row of ``length`` is scanned in: ``chunk``, or
    the row rounded up to eight where that is less."""
    chunk = min(int(chunk), -(-length // 8) * 8)
    return chunk, -(-length // chunk)


def ssd_scan(x, delta, a, b, c, skip, segment_ids=None, chunk=DEFAULT_CHUNK, interpret=False):
    """``y`` ``[rows, L, H, P]`` (``x``'s dtype) of the recurrence in the
    module's text. ``x`` ``[rows, L, H, P]``; ``delta`` ``[rows, L, H]`` (the
    step, after its softplus); ``a`` ``[H]`` float32, negative; ``b`` and
    ``c`` ``[rows, L, G, N]``, ``G`` dividing ``H``; ``skip`` ``[H]``;
    ``segment_ids`` ``int32 [rows, L]`` or None (one document a row). A row
    is padded to a multiple of its chunk (:func:`chunks_of`); what is appended
    is a document of its own after every real position and is cut off the
    result. Differentiable in all but the ids."""
    rows, length, heads, width = x.shape
    groups, states = b.shape[2], b.shape[3]
    if heads % groups or b.shape != c.shape or delta.shape != x.shape[:3] or a.shape != (heads,):
        raise ValueError("ssd scan: x {}, delta {}, a {}, b {}, c {}".format(
            x.shape, delta.shape, a.shape, b.shape, c.shape))
    if not interpret and groups > 1 and ((heads // groups * width) % 128 or states % 128):
        raise ValueError(
            "ssd scan: a group's {} heads of {} and its {} states must be multiples of 128 lanes".format(
                heads // groups, width, states))
    chunk, chunks = chunks_of(length, chunk)
    pad = chunks * chunk - length
    ids = jnp.ones((rows, length), jnp.int32) if segment_ids is None else segment_ids.astype(jnp.int32)
    with jax.named_scope("tos.ssd_scan"):
        delta = delta.astype(jnp.float32)
        xd = (x.astype(jnp.float32) * delta[..., None]).astype(x.dtype).reshape(rows, length, heads * width)
        log_decay = delta * a.astype(jnp.float32)
        b, c = b.reshape(rows, length, groups * states), c.reshape(rows, length, groups * states)
        if pad:
            xd, log_decay, b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (xd, log_decay, b, c))
            ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
        cum = jnp.cumsum(log_decay.reshape(rows, chunks, chunk, heads), axis=2).reshape(rows, chunks * chunk, heads)
        y = _scan(xd, cum, b.astype(x.dtype), c.astype(x.dtype), ids, chunk, heads, groups, bool(interpret))
        y = y[:, :length].reshape(x.shape)
        return (y.astype(jnp.float32) + skip.astype(jnp.float32)[:, None] * x.astype(jnp.float32)).astype(x.dtype)
