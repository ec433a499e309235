"""Sharding rules: how batches and parameters map onto the mesh.

The replacement for the reference's implicit placement model (every worker
holds a full replica, NCCL all-reduces gradients): here placement is explicit
`jax.sharding.NamedSharding`s, and XLA derives the collectives. Batch tensors
shard their leading dimension across the data axes (``dp`` × ``fsdp``);
parameters are replicated for pure DP or sharded along ``fsdp`` (ZeRO-3 style)
with per-array axis selection.
"""

import logging

logger = logging.getLogger(__name__)


def data_axes(mesh):
    """The mesh axes a batch's leading dim is sharded over."""
    return tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)


def batch_spec(mesh):
    """PartitionSpec for a batch: leading dim over the data axes."""
    from jax.sharding import PartitionSpec as P

    axes = data_axes(mesh)
    if not axes:
        return P()
    return P(axes if len(axes) > 1 else axes[0])


def batch_sharding(mesh):
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, batch_spec(mesh))


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def _pick_fsdp_axis(shape, axis_size, min_weight_size):
    """Index of the dim to shard along fsdp: the largest dim divisible by the
    axis size, on arrays big enough to be worth sharding; None = replicate."""
    import math

    if math.prod(shape) < min_weight_size:
        return None
    best, best_dim = None, -1
    for i, d in enumerate(shape):
        if d % axis_size == 0 and d > best_dim:
            best, best_dim = i, d
    return best


def fsdp_param_specs(params, mesh, min_weight_size=2**14):
    """PartitionSpec pytree for params: fully-shard eligible arrays along the
    ``fsdp`` axis (ZeRO-3), replicate the rest (biases, norm scales, small
    embeddings). With no ``fsdp`` axis in the mesh, everything replicates."""
    import jax
    from jax.sharding import PartitionSpec as P

    if "fsdp" not in mesh.axis_names:
        return jax.tree.map(lambda _: P(), params)
    axis_size = mesh_axis_size(mesh, "fsdp")

    def spec_for(x):
        shape = getattr(x, "shape", ())
        dim = _pick_fsdp_axis(shape, axis_size, min_weight_size)
        if dim is None:
            return P()
        spec = [None] * len(shape)
        spec[dim] = "fsdp"
        return P(*spec)

    return jax.tree.map(spec_for, params)


def _spec_axes(spec):
    """Flat set of mesh-axis names a PartitionSpec already uses."""
    used = set()
    for entry in tuple(spec):
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.update(entry)
        else:
            used.add(entry)
    return used


def overlay_fsdp_specs(params, specs, mesh, min_weight_size=2**14):
    """Overlay ZeRO-3 sharding onto an existing per-array spec tree.

    The composition rule for hybrid dp×fsdp(×tp) meshes: a model's own
    placement (e.g. :func:`tensorflowonspark_tpu.models.transformer.param_specs`
    claiming ``tp``/``fsdp`` dims) wins where it already touches the ``fsdp``
    axis; every other array big enough to be worth sharding gets its largest
    still-unclaimed dim sharded along ``fsdp``, so the optimizer state and
    per-step all-gather shrink even for arrays the model rules replicate.
    With no ``fsdp`` axis in the mesh this is the identity.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    if "fsdp" not in mesh.axis_names:
        return specs
    axis_size = mesh_axis_size(mesh, "fsdp")

    def overlay(x, s):
        import math

        if "fsdp" in _spec_axes(s):
            return s
        shape = getattr(x, "shape", ())
        if math.prod(shape) < min_weight_size:
            return s
        entries = list(tuple(s)) + [None] * (len(shape) - len(tuple(s)))
        best, best_dim = None, -1
        for i, d in enumerate(shape):
            if entries[i] is None and d % axis_size == 0 and d > best_dim:
                best, best_dim = i, d
        if best is None:
            return s
        entries[best] = "fsdp"
        return P(*entries)

    return jax.tree.map(
        overlay, params, specs, is_leaf=lambda n: isinstance(n, P)
    )


def mesh_axis_size(mesh, name):
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def shard_params(params, mesh, specs=None):
    """Place a params pytree onto the mesh (replicated or per-array specs)."""
    import jax
    from jax.sharding import NamedSharding

    if specs is None:
        specs = fsdp_param_specs(params, mesh)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


#: shard_batch's two counters, taken from the registry at its first call
_place_counters = None


def shard_batch(batch, mesh):
    """Place a host-local batch pytree onto the mesh, sharded over data axes.

    Single-process: a plain sharded ``device_put``. Multi-process (one process
    per TPU host, the TFSparkNode world): each process contributes its local
    shard via ``make_array_from_process_local_data`` — the device-side analogue
    of the reference's per-executor feed queues (each executor fed only its own
    partition; here each host's partition becomes its shard of the global
    batch).

    The call is the ``h2d_place`` span: its host seconds and the bytes it
    hands to the device are counted (``h2d_place_seconds_total``,
    ``h2d_place_bytes_total``). The copy itself is asynchronous; what the
    span holds is what the loop's thread pays for it.
    """
    import jax

    from tensorflowonspark_tpu import obs

    sharding = batch_sharding(mesh)
    if jax.process_count() == 1:
        def place(x):
            return jax.device_put(x, sharding)
    else:
        def place(x):
            return jax.make_array_from_process_local_data(sharding, x)
    if not obs.enabled():
        return jax.tree.map(place, batch)
    global _place_counters
    if _place_counters is None:
        _place_counters = (
            obs.counter(
                "h2d_place_seconds_total",
                help="host seconds inside shard_batch (placing batches on the mesh)",
            ),
            obs.counter("h2d_place_bytes_total", help="bytes shard_batch handed to the device"),
        )
    seconds, placed_bytes = _place_counters
    with obs.span("h2d_place", seconds_total=seconds):
        placed = jax.tree.map(place, batch)
    placed_bytes.inc(sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(batch)))
    return placed
