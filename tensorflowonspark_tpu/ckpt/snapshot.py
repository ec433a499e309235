"""Snapshot stage: copy the in-flight TrainState to host buffers.

The first half of the CheckFreq split (Mohan et al., FAST'21): decouple
*snapshot* (device → host, on the training thread, cheap) from *persist*
(host → storage, on the writer thread, slow). The training loop only ever
pays the D2H copy; the orbax write happens behind it.

Donation-safe by construction: the snapshot is a **new host buffer** — it
never aliases device memory, so the device state handed back to the step
loop can be donated into the next step while the writer is still
serializing the copy. ``jax.device_get`` on a CPU backend
can return a zero-copy *view* of the device buffer, which would break that
guarantee — the copy below therefore always lands in memory this module
owns.

Buffers are pooled double-buffer style (:class:`SnapshotBuffers`): with at
most one save in flight and at most one pending, two resident slots cover
the steady state, so per-snapshot allocation disappears after warm-up on
fixed-shape states (momentary overflow slots are allocated when both are
held and simply dropped on release).
"""

import logging
import threading
import time

import numpy as np

from tensorflowonspark_tpu import chaos, obs

logger = logging.getLogger(__name__)


class HostSnapshot:
    """One host-resident copy of a state pytree, tagged with its step.

    ``tree`` is the original pytree structure with every leaf replaced by
    an owned numpy array (what the writer hands to orbax); ``nbytes`` is
    the host footprint; ``slot`` is the pool slot backing the leaves (None
    for unpooled snapshots)."""

    __slots__ = ("tree", "step", "nbytes", "slot")

    def __init__(self, tree, step, nbytes, slot=None):
        self.tree = tree
        self.step = step
        self.nbytes = nbytes
        self.slot = slot


class _Slot:
    __slots__ = ("leaves", "signature")

    def __init__(self, leaves, signature):
        self.leaves = leaves
        self.signature = signature


def _leaf_to_host(leaf, out=None):
    """Copy one leaf into owned host memory (into ``out`` when shapes
    match). Returns the owned array."""
    import jax

    host = jax.device_get(leaf)
    arr = np.asarray(host)
    if out is not None:
        np.copyto(out, arr)
        return out
    if arr is leaf or isinstance(leaf, np.ndarray):
        # device_get passed a host array through unchanged — own a copy
        return np.array(arr, copy=True)
    if not arr.flags.owndata or not arr.flags.writeable:
        # zero-copy view of a (CPU) device buffer, or jax's cached assembly
        # of a sharded array (owndata but frozen read-only): either way it
        # cannot serve as a reusable pool buffer — materialize an owned,
        # writable copy
        return np.array(arr, copy=True)
    return arr


def snapshot_to_host(state, step=None, slot=None):
    """Copy ``state`` (device or host pytree) into owned host buffers.

    The barrier-free point: called right after a step returns, the copy
    waits only for *that step's* output arrays, not for any subsequently
    enqueued work. Fires the ``ckpt.snapshot_stall`` chaos site and feeds
    ``ckpt_snapshot_seconds_total`` / ``ckpt_bytes_total``.

    Returns a :class:`HostSnapshot`; pass a pool ``slot`` (from
    :class:`SnapshotBuffers`) to reuse its buffers.
    """
    import jax

    t0 = time.monotonic()
    if chaos.active:
        chaos.delay("ckpt.snapshot_stall")
    leaves, treedef = jax.tree.flatten(state)
    outs = slot.leaves if slot is not None else [None] * len(leaves)
    host_leaves = [_leaf_to_host(leaf, out) for leaf, out in zip(leaves, outs)]
    if slot is not None:
        slot.leaves = host_leaves
    tree = jax.tree.unflatten(treedef, host_leaves)
    nbytes = sum(leaf.nbytes for leaf in host_leaves)
    elapsed = time.monotonic() - t0
    obs.counter(
        "ckpt_snapshot_seconds_total",
        help="seconds the training thread spent snapshotting state to host",
    ).inc(elapsed)
    obs.counter(
        "ckpt_bytes_total", help="bytes of state snapshotted to host buffers"
    ).inc(nbytes)
    return HostSnapshot(tree, step, nbytes, slot=slot)


def _leaf_sig(leaf):
    dtype = getattr(leaf, "dtype", None)
    if dtype is None:  # python scalar leaf
        dtype = np.asarray(leaf).dtype
    return (tuple(getattr(leaf, "shape", np.shape(leaf))), np.dtype(dtype).str)


def _signature(state):
    """(treedef, leaf shapes/dtypes) — computed WITHOUT touching leaf data
    (no device sync) so slot matching is free."""
    import jax

    leaves, treedef = jax.tree.flatten(state)
    return (treedef, tuple(_leaf_sig(l) for l in leaves))


class SnapshotBuffers:
    """Bounded pool of reusable host buffer slots (default depth 2: one
    backing the in-flight write, one for the next pending snapshot).

    ``take`` copies the state into a free slot — or a fresh overflow slot
    when the pool is exhausted or the state's shapes changed — and
    ``release`` returns pooled slots for reuse. Thread-safe: ``take`` runs
    on the training thread while ``release`` runs on the writer thread.
    """

    def __init__(self, depth=2):
        self.depth = depth
        self._lock = threading.Lock()
        self._free = []
        self._resident = 0  # pooled slots in existence (free + held)

    def take(self, state, step=None):
        sig = _signature(state)
        slot = None
        with self._lock:
            for i, cand in enumerate(self._free):
                if cand.signature == sig:
                    slot = self._free.pop(i)
                    break
            if slot is None and self._free and self._resident >= self.depth:
                # free slots exist but none match: the state's shapes
                # changed — evict a stale slot so the pool re-fills with
                # the new signature instead of pinning dead buffers
                self._free.pop(0)
                self._resident -= 1
            if slot is None and self._resident < self.depth:
                slot = _Slot([None] * len(sig[1]), sig)
                self._resident += 1
        # overflow (both slots held, or shape change): unpooled snapshot
        return snapshot_to_host(state, step=step, slot=slot)

    def release(self, snap):
        slot = snap.slot
        if slot is None:
            return
        snap.slot = None
        with self._lock:
            if len(self._free) < self.depth:
                self._free.append(slot)
            else:
                self._resident -= 1
