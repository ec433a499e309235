"""The hyper-connections' residual path as four Pallas passes over the streams.

One sub-layer ``F`` of a decoder with ``n`` residual streams ``X`` ``[T, n,
d]`` (``models/decoder.HyperConnection`` has the equations) touches the
streams four times, and each touch is one kernel here that reads them once.
The streams come as ``vec(X)``, ``[T, n * d]`` with stream ``i`` at lanes ``i
* d`` on: on a chip ``[T, n, d]`` is another tiling of memory (``n`` rows a
tile), and a reshape between the two is a copy of the streams.

* :func:`read` — before ``F``: ``sum x^2`` (for ``1 / rms``), ``z = vec(X)
  phi / rms`` and, ``H_pre`` finished inside the pass from the whole row's
  ``z``, ``h = sum_i H_pre,i X_i``. It hands the streams on unchanged as its
  third result, and :func:`merge` is given *that*: the two cotangents of the
  streams then meet inside :func:`read`'s backward pass, in float32, and not
  in an addition of XLA's over three stream-sized arrays.
* :func:`merge` — after ``F``: ``X'_i = sum_j H_res,ij X_j + H_post,i y``.
* ``merge``'s backward — reads ``dX'``, ``X``, ``y`` and yields ``dX``, ``dy``,
  all ``n x n`` products ``<dX'_i, X_j>`` (``d H_res``) and the ``n`` products
  ``<dX'_i, y>`` (``d H_post``) together.
* ``read``'s backward — reads ``X``, ``dh``, ``dz`` and ``merge``'s ``dX`` and
  yields the whole ``dX`` (through ``H_pre``, ``phi`` and ``1 / rms``), the
  gradient of ``H_pre``'s pre-activation, and ``d phi`` accumulated across
  the tiles in float32.

A tile is ``tile`` whole rows of ``vec(X)`` (``[tile, n * d]``); the grid
walks the tokens. Inside a tile the elementwise work goes a chunk of rows and
of lanes at a time, so that a chunk's values stay in registers; the two
products with ``phi`` go to the MXU a tile at a time. Everything a map or
``phi`` is computed from, and every gradient of theirs, is float32; the
streams enter the products as they are stored and are accumulated in
float32. What ``H_post`` and ``H_res`` need beyond ``z`` (the clamp,
Sinkhorn) stays outside, on arrays of a few hundred kilobytes.

``pallas_call`` has no differentiation rule: :func:`read` and :func:`merge`
each carry a ``jax.custom_vjp``. ``interpret=True`` runs the kernels in the
Pallas interpreter (the CPU tests). On a chip the streams' width ``d`` has
to be a multiple of 128 lanes (a stream starts at lane ``i * d`` of its row)
and the tokens a multiple of 16: refused by name at trace time otherwise.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: what the kernels may take of a v5e's 128 MiB of VMEM, and what a tile's
#: double-buffered blocks and scratch are sized to stay under
_VMEM_LIMIT = 96 * 2 ** 20
_VMEM_BLOCKS = 40 * 2 ** 20
_MOST_ROWS = 256


def _token_tile(tokens, row_bytes):
    """Rows a tile: the most that divide ``tokens``, are whole sublane tiles
    (16 rows) and keep ``row_bytes`` a row under the kernels' VMEM."""
    most = max(16, min(_MOST_ROWS, _VMEM_BLOCKS // row_bytes))
    if tokens <= most:
        return tokens
    for tile in range(most - most % 16, 0, -16):
        if tokens % tile == 0:
            return tile
    raise ValueError(
        "hyper_connection: {} tokens a chip are no multiple of 16; pad the batch or the rows".format(tokens))


def _chunks(tile, d, most_lanes=512):
    """``(rows, lanes)`` of the chunk the elementwise loops go by: whole
    sublane tiles and whole registers' lanes where the tile and the streams'
    width have them."""
    rows = next((r for r in (16, 8) if tile % r == 0), tile)
    lanes = next((c for c in (512, 256, 128) if c <= most_lanes and d % c == 0), d)
    return rows, lanes


def _each_rows(tile, rows, body):
    def step(r, carry):
        body(pl.ds(pl.multiple_of(r * rows, rows), rows))
        return carry

    jax.lax.fori_loop(0, tile // rows, step, 0)


def _each_chunk(width, lanes, body, carry=0):
    """``carry = body(at, carry)`` for every chunk of ``lanes`` lanes of
    ``width``, ``at(start)`` the chunk's lanes from ``start`` on. A loop of
    the kernel's, not of the tracer's: the body is traced and compiled once
    whatever the width (28 chunks at the benchmark's)."""
    def step(c, carry):
        offset = c * lanes
        return body(lambda start=0: pl.ds(pl.multiple_of(start + offset, lanes), lanes), carry)

    return jax.lax.fori_loop(0, width // lanes, step, carry)


def _wide(ref, rs, lanes):
    return ref[rs, lanes].astype(jnp.float32)


def _row_sum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _columns(values, count, shape):
    """The first ``count`` columns of ``values`` ``[rows, >= count]``, each
    spread over a chunk's lanes."""
    return [jnp.broadcast_to(values[:, c:c + 1], shape) for c in range(count)]


def _h_pre(ab_ref, z, n):
    return jax.nn.sigmoid(ab_ref[0:1, :] * z[:, :n] + ab_ref[1:2, :])


# ---- the kernels ----------------------------------------------------------------------------------


def _read_kernel(x_ref, phit_ref, ab_ref, h_ref, z_ref, r_ref, hp_ref, *, n, d):
    tile = x_ref.shape[0]
    rows, lanes = _chunks(tile, d)
    raw = jax.lax.dot_general(  # vec(X) phi, [tile, k]
        x_ref[...], phit_ref[...], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    def squares(rs):
        def chunk(at, acc):
            x = _wide(x_ref, rs, at())
            return acc + x * x

        r_ref[rs, :] = _row_sum(_each_chunk(n * d, lanes, chunk, jnp.zeros((rows, lanes), jnp.float32)))

    _each_rows(tile, rows, squares)
    inv_rms = jax.lax.rsqrt(r_ref[...] * (1.0 / (n * d)))
    z = raw * inv_rms
    r_ref[...] = inv_rms
    z_ref[...] = z
    hp_ref[...] = _h_pre(ab_ref, z, n)

    def mix(rs):
        weights = _columns(hp_ref[rs, :], n, (rows, lanes))

        def chunk(at, carry):
            acc = weights[0] * _wide(x_ref, rs, at())
            for i in range(1, n):
                acc = acc + weights[i] * _wide(x_ref, rs, at(i * d))
            h_ref[rs, at()] = acc.astype(h_ref.dtype)
            return carry

        _each_chunk(d, lanes, chunk)

    _each_rows(tile, rows, mix)


def _merge_kernel(x_ref, y_ref, m_ref, o_ref, *, n, d):
    tile = x_ref.shape[0]
    rows, lanes = _chunks(tile, d)

    def mix(rs):
        weights = _columns(m_ref[rs, :], n * n + n, (rows, lanes))  # H_res row by row, then H_post

        def chunk(at, carry):
            xs = [_wide(x_ref, rs, at(j * d)) for j in range(n)]
            y = _wide(y_ref, rs, at())
            for i in range(n):
                acc = weights[i * n] * xs[0]
                for j in range(1, n):
                    acc = acc + weights[i * n + j] * xs[j]
                o_ref[rs, at(i * d)] = (acc + weights[n * n + i] * y).astype(o_ref.dtype)
            return carry

        _each_chunk(d, lanes, chunk)

    _each_rows(tile, rows, mix)


def _merge_bwd_kernel(g_ref, x_ref, y_ref, m_ref, dx_ref, dy_ref, dm_ref, *, n, d):
    tile = x_ref.shape[0]
    rows, lanes = _chunks(tile, d, most_lanes=128)  # n * n + n accumulators a chunk: keep them a register wide

    def transposed(rs):
        weights = _columns(m_ref[rs, :], n * n + n, (rows, lanes))

        def chunk(at, products):
            gs = [_wide(g_ref, rs, at(i * d)) for i in range(n)]
            xs = [_wide(x_ref, rs, at(j * d)) for j in range(n)]
            y = _wide(y_ref, rs, at())
            for j in range(n):
                acc = weights[j] * gs[0]
                for i in range(1, n):
                    acc = acc + weights[i * n + j] * gs[i]
                dx_ref[rs, at(j * d)] = acc.astype(dx_ref.dtype)
            acc = weights[n * n] * gs[0]
            for i in range(1, n):
                acc = acc + weights[n * n + i] * gs[i]
            dy_ref[rs, at()] = acc.astype(dy_ref.dtype)
            return tuple(product + g * other for product, (g, other) in zip(
                products, [(gs[i], xs[j]) for i in range(n) for j in range(n)] + [(g, y) for g in gs]))

        zero = jnp.zeros((rows, lanes), jnp.float32)
        for c, product in enumerate(_each_chunk(d, lanes, chunk, (zero,) * (n * n + n))):
            dm_ref[rs, c:c + 1] = _row_sum(product)  # <dX'_i, X_j> row by row, then <dX'_i, y>

    _each_rows(tile, rows, transposed)


def _read_bwd_kernel(x_ref, gx_ref, dh_ref, z_ref, dz_ref, r_ref, phit_ref, ab_ref,
                     dx_ref, dphit_ref, da_ref, hp_ref, co_ref, dzt_ref, dxz_ref, *, n, d):
    tile = x_ref.shape[0]
    rows, lanes = _chunks(tile, d)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphit_ref[...] = jnp.zeros_like(dphit_ref)

    z, inv_rms = z_ref[...], r_ref[...]
    hp = _h_pre(ab_ref, z, n)
    hp_ref[...] = hp

    def through_h(rs):  # d H_pre,i = <dh, X_i>
        def chunk(at, products):
            dh = _wide(dh_ref, rs, at())
            return tuple(product + dh * _wide(x_ref, rs, at(i * d)) for i, product in enumerate(products))

        zero = jnp.zeros((rows, lanes), jnp.float32)
        for i, product in enumerate(_each_chunk(d, lanes, chunk, (zero,) * n)):
            da_ref[rs, i:i + 1] = _row_sum(product)

    _each_rows(tile, rows, through_h)
    da = da_ref[...] * hp * (1.0 - hp)  # of H_pre's pre-activation
    da_ref[...] = da
    dzt_ref[...] = dz_ref[...]
    dzt_ref[:, 0:n] = dz_ref[:, 0:n] + ab_ref[0:1, :] * da
    dz = dzt_ref[...]
    # z = raw / rms: d raw = dz / rms, and through 1 / rms every x gets
    # -x (dz . z) / (rms^2 n d): a factor of x, one a row
    co_ref[...] = _row_sum(dz * z) * inv_rms * inv_rms * (-1.0 / (n * d))
    draw = (dz * inv_rms).astype(x_ref.dtype)
    dxz_ref[...] = jnp.dot(draw, phit_ref[...], preferred_element_type=jnp.float32)
    dphit_ref[...] += jax.lax.dot_general(
        draw, x_ref[...], (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    def to_streams(rs):
        weights = _columns(hp_ref[rs, :], n, (rows, lanes))
        factor = jnp.broadcast_to(co_ref[rs, :], (rows, lanes))

        def chunk(at, carry):
            dh = _wide(dh_ref, rs, at())
            for i in range(n):
                dx = (_wide(gx_ref, rs, at(i * d)) + factor * _wide(x_ref, rs, at(i * d))
                      + weights[i] * dh + dxz_ref[rs, at(i * d)])
                dx_ref[rs, at(i * d)] = dx.astype(dx_ref.dtype)
            return carry

        _each_chunk(d, lanes, chunk)

    _each_rows(tile, rows, to_streams)


# ---- the calls ------------------------------------------------------------------------------------


def _call(kernel, name, tile, sequential, interpret, inputs, outputs, scratch=()):
    """``kernel`` over the tokens' tiles. ``inputs`` (arrays) and ``outputs``
    (shape structs) come as ``(value, by_rows)``: whether the grid walks the
    value's rows a tile at a time or it stays whole in VMEM."""
    def spec(shape, by_rows):
        if by_rows:
            return pl.BlockSpec((tile,) + tuple(shape[1:]), lambda i: (i, 0))
        return pl.BlockSpec(tuple(shape), lambda i: (0, 0))

    return pl.pallas_call(
        kernel,
        grid=(inputs[0][0].shape[0] // tile,),
        in_specs=[spec(a.shape, by_rows) for a, by_rows in inputs],
        out_specs=[spec(a.shape, by_rows) for a, by_rows in outputs],
        out_shape=[a for a, _ in outputs],
        scratch_shapes=list(scratch),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if sequential else "parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(*(a for a, _ in inputs))


def _sizes(x, n, interpret):
    tokens, width = x.shape
    d = width // n
    if not interpret and d % 128:
        raise ValueError(
            "hyper_connection: streams {} wide are no multiple of the chip's 128 lanes".format(d))
    return tokens, width, d, x.dtype.itemsize


#: A step meets each call at thirty sites (ten sub-layers, forward, recomputed
#: and backward) and is traced about three times a start: under an inner
#: ``jit`` a kernel's body is traced once a shape and the sites share its
#: jaxpr (tracing and lowering the cell's step on a sandbox's CPU: 6.4 s
#: without, 4.3 s with). An operation's ``op_name`` still opens with its own
#: site's scopes and phase (``…/transpose(jvp(…))/…/tos.mhc/
#: jit(_merge_bwd_call)/mhc_merge_bwd/…``), which the trace's readers go by.
_traced_once = functools.partial(jax.jit, static_argnames="interpret")


def _rows_of(tokens, width, dtype):
    return jax.ShapeDtypeStruct((tokens, width), dtype), True


@_traced_once
def _read_call(x, phit, ab, interpret):
    n, k, f32 = ab.shape[1], phit.shape[0], jnp.float32
    tokens, width, d, size = _sizes(x, n, interpret)
    tile = _token_tile(tokens, 2 * (width + d) * size)
    return _call(
        functools.partial(_read_kernel, n=n, d=d), "mhc_read", tile, False, interpret,
        [(x, True), (phit, False), (ab, False)],
        [_rows_of(tokens, d, x.dtype), _rows_of(tokens, k, f32), _rows_of(tokens, 1, f32)],
        [pltpu.VMEM((tile, n), f32)])


@_traced_once
def _read_bwd_call(x, gx, dh, z, dz, inv_rms, phit, ab, interpret):
    n, k, f32 = ab.shape[1], phit.shape[0], jnp.float32
    tokens, width, d, size = _sizes(x, n, interpret)
    tile = _token_tile(tokens, 2 * (3 * width + d) * size + 4 * width)
    return _call(
        functools.partial(_read_bwd_kernel, n=n, d=d), "mhc_read_bwd", tile, True, interpret,
        [(x, True), (gx, True), (dh, True), (z, True), (dz, True), (inv_rms, True), (phit, False), (ab, False)],
        [_rows_of(tokens, width, x.dtype), (jax.ShapeDtypeStruct((k, width), f32), False), _rows_of(tokens, n, f32)],
        [pltpu.VMEM((tile, n), f32), pltpu.VMEM((tile, 1), f32), pltpu.VMEM((tile, k), f32),
         pltpu.VMEM((tile, width), f32)])


@_traced_once
def _merge_call(x, y, m, interpret):
    n = x.shape[1] // y.shape[1]
    tokens, width, d, size = _sizes(x, n, interpret)
    tile = _token_tile(tokens, 2 * (2 * width + d) * size)
    return _call(
        functools.partial(_merge_kernel, n=n, d=d), "mhc_merge", tile, False, interpret,
        [(x, True), (y, True), (m, True)], [_rows_of(tokens, width, x.dtype)])[0]


@_traced_once
def _merge_bwd_call(g, x, y, m, interpret):
    n = x.shape[1] // y.shape[1]
    tokens, width, d, size = _sizes(x, n, interpret)
    tile = _token_tile(tokens, 2 * (3 * width + 2 * d) * size)
    return _call(
        functools.partial(_merge_bwd_kernel, n=n, d=d), "mhc_merge_bwd", tile, False, interpret,
        [(g, True), (x, True), (y, True), (m, True)],
        [_rows_of(tokens, width, x.dtype), _rows_of(tokens, d, y.dtype), _rows_of(tokens, m.shape[1], jnp.float32)])


# ---- the two differentiable passes ----------------------------------------------------------------
# Both rules of each run under the model's scope ``tos.mhc``: the trace's
# readers book an operation by the scope in its ``op_name`` (``benchmarks/
# layer_metrics/_moe.in_scope``), in the backward pass as in the forward.

_SCOPE = "tos.mhc"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _read(x, phit, ab, interpret):
    return _read_fwd(x, phit, ab, interpret)[0]


def _read_fwd(x, phit, ab, interpret):
    with jax.named_scope(_SCOPE):
        stored = phit.astype(x.dtype)  # the product takes phi as the streams are stored
        h, z, inv_rms = _read_call(x, stored, ab, interpret)
    return (h, z, x), (x, stored, ab, z, inv_rms)


def _read_bwd(interpret, saved, cotangents):
    x, stored, ab, z, inv_rms = saved
    dh, dz, gx = cotangents
    with jax.named_scope(_SCOPE):
        dx, dphit, da = _read_bwd_call(x, gx, dh, z, dz, inv_rms, stored, ab, interpret)
        dab = jnp.stack([jnp.sum(da * z[:, :ab.shape[1]], axis=0), jnp.sum(da, axis=0)])
    return dx, dphit, dab


_read.defvjp(_read_fwd, _read_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _merge(x, y, m, interpret):
    return _merge_fwd(x, y, m, interpret)[0]


def _merge_fwd(x, y, m, interpret):
    with jax.named_scope(_SCOPE):
        return _merge_call(x, y, m, interpret), (x, y, m)


def _merge_bwd(interpret, saved, g):
    with jax.named_scope(_SCOPE):
        return tuple(_merge_bwd_call(g, *saved, interpret))


_merge.defvjp(_merge_fwd, _merge_bwd)


def read(streams, phi, alpha_pre, b_pre, interpret=False):
    """Before ``F``. ``streams`` ``[…, n * d]`` (``vec(X)``: stream ``i`` at
    lanes ``i * d`` on); ``phi`` ``float32 [n, d, k]``, its first ``n``
    columns ``H_pre``'s; ``alpha_pre`` a scalar, ``b_pre`` ``[n]``. Returns
    ``(h […, d], z float32 […, k], streams)``: ``z = vec(X) phi /
    rms(vec(X))``, ``h = sum_i sigmoid(alpha_pre z_i + b_pre,i) X_i``, and the
    streams for :func:`merge`."""
    n, d, k = phi.shape
    ab = jnp.stack([jnp.broadcast_to(alpha_pre, (n,)), b_pre]).astype(jnp.float32)
    h, z, x = _read(streams.reshape(-1, n * d), phi.reshape(n * d, k).T, ab, interpret)
    lead = streams.shape[:-1]
    return h.reshape(lead + (d,)), z.reshape(lead + (k,)), x.reshape(streams.shape)


def merge(streams, y, maps, interpret=False):
    """After ``F``: ``X'_i = sum_j H_res,ij X_j + H_post,i y``. ``streams``
    ``[…, n * d]`` as :func:`read` handed them on, ``y`` ``[…, d]``, ``maps``
    ``float32 […, n * n + n]``: ``H_res`` row by row, then ``H_post``."""
    out = _merge(streams.reshape(-1, streams.shape[-1]), y.reshape(-1, y.shape[-1]),
                 maps.reshape(-1, maps.shape[-1]), interpret)
    return out.reshape(streams.shape)
