"""A seeded count sketch of a tree of arrays, leaf by leaf.

Two gradients whose leaves have the same norms can still point apart, and the
two sides of the comparison never hold their gradients at the same time. So
each side keeps, for every leaf, ``BUCKETS`` numbers: the leaf flattened,
multiplied by seeded signs and summed into buckets. The sketch is linear — the
difference of two sketches is the sketch of the difference — and keeps the
norm in expectation (exactly, for a leaf of at most ``BUCKETS`` elements), so
``|S a - S b| / |S b|`` estimates ``|a - b| / |b|`` to about
``sqrt(2 / (BUCKETS * leaves))``. Both sides call this one function with the
same key, so the signs are the same.
"""

import zlib

import jax
import jax.numpy as jnp

BUCKETS = 256


def leaf_sketch(leaf, key, name):
    """``[BUCKETS]`` float32: the sketch of one leaf, its signs drawn from
    ``key`` and the leaf's name (call under jit)."""
    flat = leaf.astype(jnp.float32).reshape(-1)
    signs = jax.random.rademacher(
        jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF), flat.shape, jnp.float32)
    return jnp.pad(flat * signs, (0, (-flat.size) % BUCKETS)).reshape(-1, BUCKETS).sum(axis=0)


def relative_distance(got, want):
    """``|S got - S want| / |S want|`` over all the leaves of ``want`` (host
    dicts of lists); infinite where ``got`` lacks a leaf."""
    apart = size = 0.0
    for name, ref in want.items():
        if name not in got:
            return float("inf")
        apart += sum((a - b) ** 2 for a, b in zip(got[name], ref))
        size += sum(b * b for b in ref)
    return (apart / size) ** 0.5 if size else float("inf")
