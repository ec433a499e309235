"""Pipeline parallelism: a GPipe schedule over pipeline stages.

Absent from the reference (SURVEY.md §2.7: model parallelism was
"claimed-but-user-managed" TF1 device scopes; no pipeline support) — this is
a beyond-parity capability, built the TPU way:

:func:`pipeline_apply` — GPipe-style forward pipeline over the ``pp`` mesh
axis: microbatch activations rotate between neighbours with
``lax.ppermute`` (ICI neighbour links) and the whole schedule is a
``lax.scan`` inside ``shard_map`` — one compiled program, no host round
trips, fully differentiable (gradients flow back through the permutes in
reverse schedule order, which is exactly GPipe's backward). Schedule is
the classic bubble pipeline: with P stages and M microbatches, step t has
stage i working on microbatch t-i; M + P - 1 steps, bubble fraction
(P-1)/(M+P-1).
"""

import jax
import jax.numpy as jnp
from jax import lax

from tensorflowonspark_tpu.parallel.mesh import mesh_shape


def stack_stage_params(params_list):
    """[per-stage pytrees] → one pytree with a leading stage dim (shard it
    with ``PartitionSpec('pp', ...)``)."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *params_list)


def pipeline_apply(stage_fn, stacked_params, microbatches, mesh, axis="pp", batch_axis=None):
    """Run ``stage_fn`` as a P-stage pipeline over the mesh's ``axis``.

    ``stage_fn(stage_params, x) -> y`` is ONE stage's computation; every
    stage must map the same activation shape to itself (classic homogeneous
    pipeline). ``stacked_params`` has a leading stage dim of size P
    (:func:`stack_stage_params`); ``microbatches`` is ``[M, ...]`` (split a
    global batch with :func:`split_microbatches`). Returns ``[M, ...]``
    outputs, replicated over ``axis``.

    ``batch_axis`` composes the pipeline with data parallelism on one mesh:
    the within-microbatch dim (dim 1) is sharded over that axis, so a
    ``{"pp": P, "dp": D}`` mesh runs D activation shards through P stages
    concurrently — each dp column owns its slice end to end, the ppermute
    stage hops stay within the column, and params are replicated over dp.
    """
    from jax.sharding import PartitionSpec as P

    from tensorflowonspark_tpu.parallel.collectives import shard_map

    n_stages = mesh_shape(mesh)[axis]
    del n_stages  # validated implicitly by the leading-dim split below

    def _worker(params, mb):
        params = jax.tree.map(lambda a: jnp.squeeze(a, 0), params)  # my stage
        from tensorflowonspark_tpu.parallel.collectives import axis_size

        n_pp = axis_size(axis)
        idx = lax.axis_index(axis)
        n_micro = mb.shape[0]

        def body(carry, t):
            buf, out = carry
            # stage 0 injects microbatch t (clipped; masked by validity of
            # the output slot below), later stages eat their neighbour's buf
            x_in = jnp.where(idx == 0, mb[jnp.clip(t, 0, n_micro - 1)], buf)
            y = stage_fn(params, x_in)
            # the LAST stage finishes microbatch t-(P-1) at step t
            slot = t - (n_pp - 1)
            clipped = jnp.clip(slot, 0, n_micro - 1)
            out = out.at[clipped].set(
                jnp.where((idx == n_pp - 1) & (slot >= 0), y, out[clipped])
            )
            # rotate activations to the next stage (ICI neighbour hop)
            perm = [(i, (i + 1) % n_pp) for i in range(n_pp)]
            buf = lax.ppermute(y, axis, perm=perm)
            return (buf, out), None

        init = (jnp.zeros_like(mb[0]), jnp.zeros_like(mb))
        (_, out), _ = lax.scan(body, init, jnp.arange(mb.shape[0] + n_pp - 1))
        # only the last stage holds real outputs; broadcast so the result is
        # replicated over the pp axis (cheap at microbatch scale)
        return lax.psum(jnp.where(idx == n_pp - 1, out, jnp.zeros_like(out)), axis)

    data_spec = P(None, batch_axis) if batch_axis else P()
    return shard_map(
        _worker,
        mesh=mesh,
        in_specs=(P(axis), data_spec),
        out_specs=data_spec,
        check_vma=False,
    )(stacked_params, microbatches)


def split_microbatches(x, n_micro):
    """[B, ...] → [n_micro, B//n_micro, ...] (static shapes for the scan)."""
    if x.shape[0] % n_micro:
        raise ValueError(
            "batch {} not divisible into {} microbatches".format(x.shape[0], n_micro)
        )
    return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])


def merge_microbatches(y):
    """Inverse of :func:`split_microbatches`."""
    return y.reshape((-1,) + y.shape[2:])
