"""XLA collective wrappers — the NCCL/RING replacement.

The reference's collective layer was TensorFlow's `CollectiveAllReduce` over
NCCL/gRPC, selected by `all_reduce_alg`/`num_packs` flags
(/root/reference/examples/resnet/resnet_cifar_dist.py:104-105). On TPU the
equivalents are XLA collectives over ICI, emitted either implicitly by `pjit`
from shardings or explicitly inside `shard_map` bodies via these wrappers.

These are deliberately thin: the value they add is (a) one place that
documents the NCCL→XLA mapping, (b) axis-name defaulting over the canonical
data axes, (c) a `shard_map`-friendly surface for the strategy layer and ring
attention.

NCCL / TF collective      → XLA / jax primitive
-------------------------   ------------------------------------
all_reduce (sum/mean)     → lax.psum / lax.pmean
all_gather                → lax.all_gather
reduce_scatter            → lax.psum_scatter
send/recv ring            → lax.ppermute
all_to_all (a2a SP/EP)    → lax.all_to_all
broadcast                 → implicit (replicated sharding)
"""

import jax
from jax import lax


#: per-shard bodies (ring attention, the in-jit pipeline, the flash kernel)
#: take ``shard_map`` from here, beside the collectives they call
shard_map = jax.shard_map


def psum(x, axis_name):
    """All-reduce sum over a mesh axis (NCCL allreduce equivalent)."""
    return lax.psum(x, axis_name)


def pmean(x, axis_name):
    """All-reduce mean — gradient averaging for sync data parallelism."""
    return lax.pmean(x, axis_name)


def all_gather(x, axis_name, axis=0, tiled=True):
    """Gather shards from every member of the axis (NCCL allgather)."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, scatter_dimension=0, tiled=True):
    """Reduce-then-scatter (NCCL reducescatter); the building block of ZeRO
    gradient sharding."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension, tiled=tiled)


def ring_shift(x, axis_name, shift=1):
    """Rotate shards around the axis ring: member i's value goes to i+shift.

    The ppermute pattern behind ring attention and pipelined collectives; on
    TPU this maps onto neighbour ICI links.
    """
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm=perm)


def all_to_all(x, axis_name, split_axis, concat_axis, tiled=True):
    """All-to-all — the Ulysses-style sequence-parallel exchange and the MoE
    expert dispatch primitive."""
    return lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled
    )


def axis_index(axis_name):
    return lax.axis_index(axis_name)


def axis_size(axis_name):
    """Static size of a mesh axis from inside a collective body."""
    return lax.axis_size(axis_name)
