"""Device-mesh construction over ICI/DCN.

The mesh is the foundation of every parallelism strategy (SURVEY.md §2.7): the
reference's sync data parallelism (``MultiWorkerMirroredStrategy`` + NCCL ring)
becomes a 1-D ``dp`` mesh; its async PS path has no TPU analogue and is served
by the same sync mesh; TP/PP/SP/EP — absent from the reference — are additional
axes on the same mesh, so adding them is a sharding change, not a rewrite
(SURVEY.md §7 hard part 6).
"""

import logging
import math

logger = logging.getLogger(__name__)

#: canonical axis order; meshes are always built with axes in this order so
#: collectives ride ICI for the innermost (fastest-varying) axes.
AXIS_ORDER = ("dp", "fsdp", "tp", "sp", "ep", "pp")


def _normalize_axes(axes, num_devices):
    """Resolve an axes spec into an ordered {name: size} with product == num_devices.

    ``axes`` may be None (pure dp), a dict (one size may be -1 = "fill"), or a
    sequence of (name, size) pairs. Unknown axis names are allowed (appended
    after the canonical ones, in given order) so user code can define custom
    axes (e.g. a "stage" axis for pipeline parallelism).
    """
    if axes is None:
        axes = {"dp": -1}
    if not isinstance(axes, dict):
        axes = dict(axes)
    known = [a for a in AXIS_ORDER if a in axes]
    extra = [a for a in axes if a not in AXIS_ORDER]
    ordered = known + extra

    fills = [a for a in ordered if axes[a] == -1]
    if len(fills) > 1:
        raise ValueError("at most one axis may have size -1 (got {})".format(fills))
    fixed = math.prod(axes[a] for a in ordered if axes[a] != -1)
    if fills:
        if num_devices % fixed != 0:
            raise ValueError(
                "cannot fill axis {!r}: {} devices not divisible by {}".format(
                    fills[0], num_devices, fixed
                )
            )
        axes = dict(axes)
        axes[fills[0]] = num_devices // fixed
        fixed = num_devices
    if fixed != num_devices:
        raise ValueError(
            "mesh axes {} use {} devices but {} are available".format(
                {a: axes[a] for a in ordered}, fixed, num_devices
            )
        )
    return {a: axes[a] for a in ordered}


def _warn_if_multi_slice(devices):
    """Detect when the device set spans distinct TPU slices.

    Multi-slice worlds (TPU v4+ megascale / multi-pod DCN) expose a
    ``slice_index`` on each device; a plain reshape interleaves slices, so
    mesh-neighbour collectives cross the slow DCN boundary instead of riding
    ICI. Returns the set of distinct slice indices (empty when the attribute
    is absent) so tests can probe the detection with fake device objects.
    :func:`build_mesh` delegates to :func:`build_hybrid_mesh` when more than
    one slice is present, so this only warns if that delegation failed and
    the flat reshape is about to happen anyway.
    """
    slices = {
        getattr(d, "slice_index") for d in devices if getattr(d, "slice_index", None) is not None
    }
    if len(slices) > 1:
        logger.warning(
            "devices span %d distinct slices (slice_index %s) but the mesh is "
            "a flat reshape — inner-axis collectives will cross the DCN "
            "boundary. Use build_hybrid_mesh (ICI axes inner, DCN axes outer) "
            "with axis sizes that factor over the slices instead.",
            len(slices),
            sorted(slices),
        )
    return slices


def _slice_groups(devices):
    """Group devices by ``slice_index``: {slice_index: [devices]} in slice
    order, devices keeping their given order within each slice. Devices with
    no ``slice_index`` attribute all land in one group keyed ``None``."""
    groups = {}
    for d in devices:
        groups.setdefault(getattr(d, "slice_index", None), []).append(d)
    return {k: groups[k] for k in sorted(groups, key=lambda s: (s is None, s))}


def _hybrid_factors(shape, n_slices, dcn_axes):
    """Split each mesh axis into (dcn_factor, ici_factor) with
    ``prod(dcn_factors) == n_slices``.

    ``dcn_axes`` may be a dict {axis: dcn_factor} (explicit split) or a
    sequence of axis names eligible to absorb the DCN dimension — the whole
    ``n_slices`` factor goes to the first eligible axis whose size it
    divides (``dp`` by default), so a flat ``{"dp": 8}`` over 2 slices
    becomes dp = 2 (DCN, outer) x 4 (ICI, inner).
    """
    if isinstance(dcn_axes, dict):
        factors = {a: int(dcn_axes.get(a, 1)) for a in shape}
        bad = [a for a in factors if shape[a] % factors[a] != 0]
        if bad:
            raise ValueError(
                "dcn factor does not divide axis size for {}".format(
                    {a: (factors[a], shape[a]) for a in bad}
                )
            )
        if math.prod(factors.values()) != n_slices:
            raise ValueError(
                "dcn factors {} must multiply to the slice count {}".format(
                    factors, n_slices
                )
            )
        return factors
    factors = {a: 1 for a in shape}
    for a in dcn_axes:
        if a in shape and shape[a] % n_slices == 0:
            factors[a] = n_slices
            return factors
    raise ValueError(
        "no axis in {} (sizes {}) can absorb the DCN dimension of {} slices".format(
            tuple(dcn_axes), dict(shape), n_slices
        )
    )


def _hybrid_device_grid(shape, dcn_factors, groups):
    """Device ndarray for a hybrid mesh: slice-major within every axis.

    Each axis of size ``s`` splits as ``d x i`` (``d`` = its DCN factor):
    the grid is built as ``[d0, d1, ..., i0, i1, ...]`` — per-slice blocks
    reshaped to the ICI dims, stacked over the DCN dims — then the paired
    dims are interleaved and merged, so walking any mesh axis visits all
    within-slice (ICI) neighbours before crossing a slice (DCN) boundary.
    Pure numpy over opaque device objects, so tests can drive it with fakes.
    """
    import numpy as np

    ordered = list(shape)
    dcn_dims = tuple(dcn_factors[a] for a in ordered)
    ici_dims = tuple(shape[a] // dcn_factors[a] for a in ordered)
    per_slice = math.prod(ici_dims)
    blocks = []
    for idx, devs in groups.items():
        if len(devs) != per_slice:
            raise ValueError(
                "slice {} has {} devices; hybrid mesh needs {} per slice".format(
                    idx, len(devs), per_slice
                )
            )
        block = np.empty(per_slice, dtype=object)
        block[:] = devs
        blocks.append(block.reshape(ici_dims))
    grid = np.stack(blocks).reshape(dcn_dims + ici_dims)
    n = len(ordered)
    perm = [k for pair in ((i, n + i) for i in range(n)) for k in pair]
    return grid.transpose(perm).reshape(tuple(shape.values()))


def build_hybrid_mesh(axes=None, devices=None, dcn_axes=("dp",), drop_trivial=False):
    """Build a slice-topology-aware mesh: DCN axes outer, ICI axes inner.

    The real placement behind the old multi-slice warning: on worlds whose
    devices carry distinct ``slice_index`` values (TPU multi-slice / multi-pod
    DCN), collectives along an axis that spans slices pay the slow DCN hop, so
    the data-parallel axis should cross slices while fsdp/tp/sp stay inside
    one slice on ICI. ``dcn_axes`` names the axes allowed to absorb the
    cross-slice dimension (first fit wins; pass a ``{axis: factor}`` dict to
    split explicitly). ``axes=None`` defaults to ``{"dp": n_slices,
    "fsdp": -1}`` — dp across slices, params fully sharded within each slice.

    Single-slice (or slice-unaware) device sets delegate straight to
    :func:`build_mesh`. On TPU the placement goes through
    ``mesh_utils.create_hybrid_device_mesh`` (a refusal raises); elsewhere
    the grid is assembled slice-major by :func:`_hybrid_device_grid`.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    groups = _slice_groups(devices)
    if len(groups) <= 1:
        return build_mesh(axes, devices, drop_trivial)
    n_slices = len(groups)
    if axes is None:
        axes = {"dp": n_slices, "fsdp": -1}
    shape = _normalize_axes(axes, len(devices))
    factors = _hybrid_factors(shape, n_slices, dcn_axes)
    if drop_trivial:
        kept = {a: s for a, s in shape.items() if s > 1} or {"dp": 1}
        if any(factors[a] > 1 for a in shape if a not in kept):
            raise ValueError("cannot drop a trivial axis carrying a DCN factor")
        shape = kept
        factors = {a: factors[a] for a in shape}

    platform = getattr(devices[0], "platform", "cpu") if len(devices) else "cpu"
    if platform == "tpu":
        # a placement the physical topology refuses is an error, not a
        # reason to fall back to an order that ignores ICI
        from jax.experimental import mesh_utils

        mesh_devices = mesh_utils.create_hybrid_device_mesh(
            tuple(shape[a] // factors[a] for a in shape),
            tuple(factors[a] for a in shape),
            devices=devices,
        )
    else:
        mesh_devices = _hybrid_device_grid(shape, factors, groups)
    logger.info(
        "hybrid mesh: %s over %d slice(s), dcn factors %s", shape, n_slices, factors
    )
    return Mesh(mesh_devices, tuple(shape.keys()))


def build_mesh(axes=None, devices=None, drop_trivial=False):
    """Build a :class:`jax.sharding.Mesh` with named axes over the devices.

    On real TPU hardware the physical layout comes from
    ``mesh_utils.create_device_mesh`` so that neighbouring mesh coordinates are
    ICI neighbours and XLA collectives ride the torus (a shape the topology
    refuses raises); on CPU/virtual devices a plain reshape is used.

    ``axes``: dict of axis name → size; one size may be -1 ("use remaining
    devices"); default ``{"dp": -1}``. ``drop_trivial`` removes size-1 axes.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    # multi-slice worlds need a hybrid (ICI-inner / DCN-outer) layout that
    # neither create_device_mesh nor a flat reshape provides — delegate;
    # only if no axis can absorb the slice dimension fall through to the
    # flat reshape (with the old warning)
    if len(_slice_groups(devices)) > 1:
        try:
            return build_hybrid_mesh(axes, devices, drop_trivial=drop_trivial)
        except ValueError as e:
            logger.warning("hybrid mesh placement failed (%s); flat reshape", e)
            _warn_if_multi_slice(devices)
    shape = _normalize_axes(axes, len(devices))
    if drop_trivial:
        shape = {a: s for a, s in shape.items() if s > 1} or {"dp": 1}

    dims = tuple(shape.values())
    platform = devices[0].platform if devices else "cpu"
    if platform == "tpu":
        # a shape the physical topology refuses is an error: device order
        # would put mesh neighbours on non-adjacent chips without saying so
        from jax.experimental import mesh_utils

        mesh_devices = mesh_utils.create_device_mesh(dims, devices=devices)
    else:
        import numpy as np

        mesh_devices = np.asarray(devices).reshape(dims)
    logger.info("mesh: %s over %d %s device(s)", shape, len(devices), platform)
    return Mesh(mesh_devices, tuple(shape.keys()))


def local_mesh(axes=None):
    """Mesh over this process's addressable devices only (single-host)."""
    import jax

    return build_mesh(axes, devices=jax.local_devices())


def mesh_shape(mesh):
    """{axis: size} for a mesh."""
    return dict(zip(mesh.axis_names, mesh.devices.shape))
