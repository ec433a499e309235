"""The selective scan of a state-space layer (Mamba, arXiv:2312.00752) as two
pallas TPU kernels with a custom VJP, restarting at every document boundary
of a packed row.

Per channel ``d`` of ``D`` and state ``n`` of ``N``, along the positions of a
row::

    Delta_t = softplus(dt_t)                                  [D]
    h_t     = keep_t * exp(Delta_t (x) A) * h_{t-1} + (Delta_t * x_t) (x) B_t    [D, N]
    y_t     = h_t C_t + D_skip * x_t                          [D]

``keep_t`` is 0 at a document's first position (``segment_ids``: where the id
differs from the position before, and at the row's start) and 1 elsewhere: a
document starts from a state of zero, whatever shares its row. ``Delta``, the
exponential, the state and ``y`` are float32 whatever the operands' dtype;
``y`` is rounded to ``x``'s dtype as it is written.

**Why a kernel.** The state is ``[D, N]`` float32 a position: 2.7 GB a row of
8192 at ``D`` 5120, ``N`` 16, and ``lax.scan`` or ``associative_scan`` in XLA
write it to HBM, several times a pass. The kernels keep the state in VMEM
(``[N, block_d]``: states on sublanes, channels on lanes, a block of
``block_d`` channels at a time) and write it to HBM **at chunk boundaries
only**: the state a chunk of ``chunk`` positions starts from, ``[rows, L /
chunk, N, D]`` float32 (10.5 MB a row of 8192 in chunks of 256). The backward
kernel walks the chunks from the last to the first; in each it first runs the
chunk forward again from its stored boundary state, keeping the chunk's
states in VMEM (``[chunk + 1, N, block_d]``), then walks it backward with the
states' cotangent carried from position to position and from chunk to chunk.

**Layout.** Everything a position contributes is a row ``[1, block_d]``
(``Delta``, ``x``, ``dy``: one value a channel, broadcast over the states'
sublanes) or a column over the states (``B_t``, ``C_t``: one value a state,
the same for every channel). A column broadcast over lanes would be a
lane-to-sublane move a position; instead ``B`` and ``C`` ride into the
kernels already widened to the register's lanes, ``[rows, L, N, 128]``
float32, a position's ``[N, 128]`` tile the same value in every lane (XLA
writes them, 67 MB each a row of 8192; the grid is ``(rows, chunks, channel
blocks)`` with the channel blocks innermost, so a chunk's tiles are fetched
once for all its channel blocks), and their cotangents leave the backward the
same way, a lane's share of the sum over channels, summed over lanes by XLA.
``keep`` rides ``[rows, L, 128]`` likewise. Positions are walked eight at a
time: one aligned ``[8, block_d]`` load a quantity, rows picked by static
slices, ``y``'s eight rows stored as one tile.

The gradient of ``A`` is accumulated in its resident output block over all of
a row's positions (``[rows, blocks, N, block_d]``, summed over rows by XLA);
that of ``D_skip`` is one fused reduction of XLA's.

**What a recomputed layer keeps.** The call's results, ``y`` and the boundary
states, pass through ``checkpoint_name`` (:data:`KEPT_SCANNED`,
:data:`KEPT_SCAN_STATE`): a model that recomputes its layers under a policy
that saves them does not run the forward kernel a second time (84 + 10.5 MB a
layer at the sizes above); without such a policy the names are identities.

The kernels are named ``ssm_scan_fwd`` and ``ssm_scan_bwd``.
``interpret=True`` runs them on the CPU for tests.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_ROWS = 8  # positions walked together: one register's sublanes

#: positions between two stored boundary states
DEFAULT_CHUNK = 256
#: channels a grid step holds (the forward's and the backward's own: the
#: backward carries three times the live registers a position)
DEFAULT_BLOCK_D = 512
DEFAULT_BLOCK_D_BWD = 256

KEPT_SCANNED = "tos.ssm_y"
KEPT_SCAN_STATE = "tos.ssm_state"

_VMEM_LIMIT = 64 * 2 ** 20


def _tile(v, width):
    """``[rows, 128]``, a row's value in every lane, as ``[rows, width]``."""
    return v if width == _LANES else pltpu.repeat(v, width // _LANES, axis=1)


def _fold(v):
    """``[rows, width]`` -> ``[rows, 128]`` whose lanes sum to the rows'
    sums: registers added onto one another, no reduction across lanes."""
    return sum(v[:, at:at + _LANES] for at in range(0, v.shape[1], _LANES))


def _softplus(v):
    return jnp.maximum(v, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(v)))


def _fwd_kernel(dt_ref, x_ref, bx_ref, cx_ref, keep_ref, a_ref, skip_ref, y_ref, hb_ref, h_all, delta_s, u_s, y_s,
                *, chunk, block_d):
    c, j = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _start():
        h_all[j] = jnp.zeros(h_all.shape[1:], jnp.float32)

    h0 = h_all[j]
    hb_ref[0, 0] = h0
    xs = x_ref[0].astype(jnp.float32)
    delta = _softplus(dt_ref[0].astype(jnp.float32))
    delta_s[...] = delta
    u_s[...] = delta * xs
    a = a_ref[...]

    def eight(i, h):
        base = pl.multiple_of(i * _ROWS, _ROWS)
        d8, u8, k8 = delta_s[pl.ds(base, _ROWS), :], u_s[pl.ds(base, _ROWS), :], keep_ref[0, pl.ds(base, _ROWS), :]
        rows = []
        for r in range(_ROWS):
            decay = jnp.exp(d8[r:r + 1] * a) * _tile(k8[r:r + 1], block_d)
            h = decay * h + u8[r:r + 1] * _tile(bx_ref[0, base + r], block_d)
            rows.append(jnp.sum(h * _tile(cx_ref[0, base + r], block_d), axis=0, keepdims=True))
        y_s[pl.ds(base, _ROWS), :] = jnp.concatenate(rows, axis=0)
        return h

    h_all[j] = jax.lax.fori_loop(0, chunk // _ROWS, eight, h0)
    y_ref[0] = (y_s[...] + skip_ref[...] * xs).astype(y_ref.dtype)


def _bwd_kernel(dt_ref, x_ref, bx_ref, cx_ref, keep_ref, a_ref, skip_ref, hb_ref, dy_ref,
                ddt_ref, dx_ref, dbx_ref, dcx_ref, da_ref,
                g_all, hs, delta_s, u_s, x_s, dy_s, ddelta_s, dx_s, *, chunk, block_d):
    c, j = pl.program_id(1), pl.program_id(2)  # c counts the chunks from the row's last

    @pl.when(c == 0)
    def _start():
        g_all[j] = jnp.zeros(g_all.shape[1:], jnp.float32)
        da_ref[0, j] = jnp.zeros(da_ref.shape[2:], jnp.float32)

    @pl.when(j == 0)
    def _start_chunk():
        dbx_ref[0] = jnp.zeros(dbx_ref.shape[1:], jnp.float32)
        dcx_ref[0] = jnp.zeros(dcx_ref.shape[1:], jnp.float32)

    dt = dt_ref[0].astype(jnp.float32)
    delta = _softplus(dt)
    x_s[...] = x_ref[0].astype(jnp.float32)
    dy_s[...] = dy_ref[0].astype(jnp.float32)
    delta_s[...] = delta
    u_s[...] = delta * x_s[...]
    a = a_ref[...]

    # the chunk forward again from its boundary state: hs[t + 1] is the state after position t
    hs[0] = hb_ref[0, 0]

    def forward_eight(i, h):
        base = pl.multiple_of(i * _ROWS, _ROWS)
        d8, u8, k8 = delta_s[pl.ds(base, _ROWS), :], u_s[pl.ds(base, _ROWS), :], keep_ref[0, pl.ds(base, _ROWS), :]
        for r in range(_ROWS):
            decay = jnp.exp(d8[r:r + 1] * a) * _tile(k8[r:r + 1], block_d)
            h = decay * h + u8[r:r + 1] * _tile(bx_ref[0, base + r], block_d)
            hs[base + r + 1] = h
        return h

    jax.lax.fori_loop(0, chunk // _ROWS, forward_eight, hs[0])

    def backward_eight(i, w):
        """``w``: what the positions after this one hand back to its state,
        ``decay_{t+1} * g_{t+1}``."""
        base = pl.multiple_of((chunk // _ROWS - 1 - i) * _ROWS, _ROWS)
        d8, u8, k8 = delta_s[pl.ds(base, _ROWS), :], u_s[pl.ds(base, _ROWS), :], keep_ref[0, pl.ds(base, _ROWS), :]
        x8, dy8 = x_s[pl.ds(base, _ROWS), :], dy_s[pl.ds(base, _ROWS), :]
        ddelta, dx = [None] * _ROWS, [None] * _ROWS
        for r in reversed(range(_ROWS)):
            t = base + r
            g = w + dy8[r:r + 1] * _tile(cx_ref[0, t], block_d)
            dcx_ref[0, t] += _fold(dy8[r:r + 1] * hs[t + 1])
            dbx_ref[0, t] += _fold(g * u8[r:r + 1])
            du = jnp.sum(g * _tile(bx_ref[0, t], block_d), axis=0, keepdims=True)  # [1, block_d]
            w = jnp.exp(d8[r:r + 1] * a) * _tile(k8[r:r + 1], block_d) * g
            dlog = w * hs[t]  # the cotangent of Delta_t (x) A
            ddelta[r] = jnp.sum(dlog * a, axis=0, keepdims=True) + du * x8[r:r + 1]
            da_ref[0, j] += dlog * d8[r:r + 1]
            dx[r] = du * d8[r:r + 1]
        ddelta_s[pl.ds(base, _ROWS), :] = jnp.concatenate(ddelta, axis=0)
        dx_s[pl.ds(base, _ROWS), :] = jnp.concatenate(dx, axis=0)
        return w

    g_all[j] = jax.lax.fori_loop(0, chunk // _ROWS, backward_eight, g_all[j])
    ddt_ref[0] = (ddelta_s[...] * jax.nn.sigmoid(dt)).astype(ddt_ref.dtype)
    dx_ref[0] = (dx_s[...] + skip_ref[...] * dy_s[...]).astype(dx_ref.dtype)


def _compiler_params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)


def _scan_fwd(dt, x, bx, cx, keep, a_t, skip, chunk, block_d, interpret):
    rows, length, width = x.shape
    states = a_t.shape[0]
    chunks, blocks = length // chunk, width // block_d
    row = pl.BlockSpec((1, chunk, block_d), lambda b, c, j: (b, c, j))
    wide = pl.BlockSpec((1, chunk, states, _LANES), lambda b, c, j: (b, c, 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, block_d=block_d),
        grid=(rows, chunks, blocks),
        in_specs=[
            row, row, wide, wide,
            pl.BlockSpec((1, chunk, _LANES), lambda b, c, j: (b, c, 0)),
            pl.BlockSpec((states, block_d), lambda b, c, j: (0, j)),
            pl.BlockSpec((1, block_d), lambda b, c, j: (0, j)),
        ],
        out_specs=[row, pl.BlockSpec((1, 1, states, block_d), lambda b, c, j: (b, c, 0, j))],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((rows, chunks, states, width), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blocks, states, block_d), jnp.float32),
            pltpu.VMEM((chunk, block_d), jnp.float32),
            pltpu.VMEM((chunk, block_d), jnp.float32),
            pltpu.VMEM((chunk, block_d), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="ssm_scan_fwd",
    )(dt, x, bx, cx, keep, a_t, skip)


def _scan_bwd(dt, x, bx, cx, keep, a_t, skip, bound, dy, chunk, block_d, interpret):
    rows, length, width = x.shape
    states = a_t.shape[0]
    chunks, blocks = length // chunk, width // block_d
    back = lambda c: chunks - 1 - c  # noqa: E731
    row = pl.BlockSpec((1, chunk, block_d), lambda b, c, j: (b, back(c), j))
    wide = pl.BlockSpec((1, chunk, states, _LANES), lambda b, c, j: (b, back(c), 0, 0))
    f32 = lambda *shape: pltpu.VMEM(shape, jnp.float32)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, block_d=block_d),
        grid=(rows, chunks, blocks),
        in_specs=[
            row, row, wide, wide,
            pl.BlockSpec((1, chunk, _LANES), lambda b, c, j: (b, back(c), 0)),
            pl.BlockSpec((states, block_d), lambda b, c, j: (0, j)),
            pl.BlockSpec((1, block_d), lambda b, c, j: (0, j)),
            pl.BlockSpec((1, 1, states, block_d), lambda b, c, j: (b, back(c), 0, j)),
            row,
        ],
        out_specs=[row, row, wide, wide, pl.BlockSpec((1, blocks, states, block_d), lambda b, c, j: (b, 0, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(dt.shape, dt.dtype),
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(bx.shape, jnp.float32),
            jax.ShapeDtypeStruct(cx.shape, jnp.float32),
            jax.ShapeDtypeStruct((rows, blocks, states, block_d), jnp.float32),
        ],
        scratch_shapes=[
            f32(blocks, states, block_d), f32(chunk + 1, states, block_d),
            f32(chunk, block_d), f32(chunk, block_d), f32(chunk, block_d), f32(chunk, block_d),
            f32(chunk, block_d), f32(chunk, block_d),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="ssm_scan_bwd",
    )(dt, x, bx, cx, keep, a_t, skip, bound, dy)


def _widen(v):
    """``[rows, L, N]`` -> ``[rows, L, N, 128]`` float32, every lane the value."""
    return jnp.broadcast_to(v.astype(jnp.float32)[..., None], v.shape + (_LANES,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _scan(dt, x, b, c, a, skip, keep, chunk, block_d, block_d_bwd, interpret):
    return _scan_vjp_fwd(dt, x, b, c, a, skip, keep, chunk, block_d, block_d_bwd, interpret)[0]


def _scan_vjp_fwd(dt, x, b, c, a, skip, keep, chunk, block_d, block_d_bwd, interpret):
    with jax.named_scope("tos.ssm_scan"):
        y, bound = _scan_fwd(dt, x, _widen(b), _widen(c), _widen(keep), a.T, skip[None, :], chunk, block_d, interpret)
        # the call's only results, out and residual both: a recomputed pass that keeps them has no use for the call
        y, bound = checkpoint_name(y, KEPT_SCANNED), checkpoint_name(bound, KEPT_SCAN_STATE)
    return y, (dt, x, b, c, a, skip, keep, bound)


def _scan_vjp_bwd(chunk, block_d, block_d_bwd, interpret, res, dy):
    dt, x, b, c, a, skip, keep, bound = res
    with jax.named_scope("tos.ssm_scan"):
        ddt, dx, dbx, dcx, da = _scan_bwd(
            dt, x, _widen(b), _widen(c), _widen(keep), a.T, skip[None, :], bound, dy, chunk, block_d_bwd, interpret)
        db, dc = (jnp.sum(t, axis=-1).astype(like.dtype) for t, like in ((dbx, b), (dcx, c)))
        # [rows, blocks, N, block_d] -> [D, N]
        da = jnp.sum(da, axis=0).transpose(1, 0, 2).reshape(a.shape[1], a.shape[0]).T
        dskip = jnp.sum(dy.astype(jnp.float32) * x.astype(jnp.float32), axis=(0, 1))
    return ddt, dx, db, dc, da.astype(a.dtype), dskip.astype(skip.dtype), None


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def _block(width, most):
    for size in (most, 512, 256, 128):
        if size <= most and width % size == 0:
            return size
    raise ValueError("selective scan: {} channels are not a multiple of {}".format(width, _LANES))


def restarts(segment_ids):
    """``bool [rows, L]``: the positions a document starts at (the row's first
    among them): where the scan, and the convolution before it, start anew."""
    first = jnp.ones(segment_ids.shape[:1] + (1,), bool)
    return jnp.concatenate([first, segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1)


def selective_scan(dt, x, b, c, a, skip, segment_ids=None, chunk=DEFAULT_CHUNK, block_d=DEFAULT_BLOCK_D,
                   block_d_bwd=DEFAULT_BLOCK_D_BWD, interpret=False):
    """``y`` ``[rows, L, D]`` (``x``'s dtype) of the recurrence in the
    module's text. ``dt`` (before its softplus) and ``x`` ``[rows, L, D]``;
    ``b`` and ``c`` ``[rows, L, N]``; ``a`` ``[D, N]`` float32, negative
    (``-exp(A_log)``); ``skip`` ``[D]`` float32; ``segment_ids`` ``int32
    [rows, L]`` or None (one document a row). ``D`` a multiple of 128, ``N``
    of 8. A row is padded to a multiple of its chunk (``chunk``, or the row's
    length rounded up to eight where that is less); what is appended comes
    after every real position and is cut off the result. Differentiable in
    all but the ids."""
    rows, length, width = x.shape
    states = a.shape[1]
    if width % _LANES or states % _ROWS or a.shape != (width, states):
        raise ValueError("selective scan: channels {} (a multiple of 128), states {} (of 8), a {}".format(
            width, states, a.shape))
    chunk = min(int(chunk), -(-length // _ROWS) * _ROWS)
    pad = (-length) % chunk
    keep = jnp.ones((rows, length), jnp.float32).at[:, 0].set(0.0) if segment_ids is None else (
        1.0 - restarts(segment_ids).astype(jnp.float32))
    if pad:
        dt, x, b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (dt, x, b, c))
        keep = jnp.pad(keep, ((0, 0), (0, pad)))
    y = _scan(dt, x, b, c, a.astype(jnp.float32), skip.astype(jnp.float32), keep, chunk,
              _block(width, int(block_d)), _block(width, int(block_d_bwd)), bool(interpret))
    return y[:, :length] if pad else y
