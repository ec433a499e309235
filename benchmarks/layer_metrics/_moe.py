"""What the ``moe_*`` readers share: device time of the traced steps by the
scope a mechanism runs under. ``models/decoder.py`` names its parts with
``jax.named_scope`` (``tos.mla``, ``tos.moe_route``, ``tos.moe_experts``,
``tos.moe_shared``, ``tos.dense_mlp``, ``tos.mhc``); an operation's
``op_name`` holds the scope in the forward pass, the backward pass and a
recomputed block alike, and a fusion carries its root's.

The grouped products of the routed experts are the exception: XLA expands
``jax.lax.ragged_dot`` into Mosaic kernels of its own and names them
``ragged-dot-none`` (the product) and ``ragged-dot-metadata`` (the group
offsets), with nothing of the scope they were traced under (chip's compiler
and my chip run, PR 26). They are the only ragged products in the step, so
they are found by that name and booked with ``tos.moe_experts``.

A program without these scopes (the parent of the PR that brought them)
reads None everywhere.
"""

import os

from benchmarks import flops_moe_lm, trace_reduce
from benchmarks.layer_metrics import _program

#: what XLA calls the grouped product's kernels: the whole ``op_name``
GROUPED_PRODUCT = "ragged-dot-"


def device_ops(run):
    """``({plane: [(op_name, start, end)]}, window)`` of the run's own
    trace, read once; None where there is none."""
    if not run.get("trace"):
        return None
    if "_device_ops" not in run:
        trace_dir = os.path.join(_program.ROOT, ".bench_scratch", run["workload"], "trace")
        try:
            run["_device_ops"] = _program.load_device_ops(trace_reduce.newest_xplane(trace_dir))
        except FileNotFoundError:
            run["_device_ops"] = None
    return run["_device_ops"]


def in_scope(op_name, scope):
    return ("/" + scope + "/") in ("/" + op_name + "/")


def seconds_where(devices, window, wanted):
    """Device seconds (mean over chips) inside ``window`` of the operations
    whose ``op_name`` satisfies ``wanted``, or None where none does."""
    lo, hi = window or (float("-inf"), float("inf"))
    seconds, found = 0.0, False
    for ops in devices.values():
        for name, start, end in ops:
            if wanted(name) and min(end, hi) > max(start, lo):
                seconds += min(end, hi) - max(start, lo)
                found = True
    return seconds / len(devices) if found else None


def is_grouped_product(op_name):
    return op_name.startswith(GROUPED_PRODUCT)


def share_pct(run, wanted):
    """Device time of the operations whose ``op_name`` satisfies ``wanted``
    over device busy time, in percent."""
    loaded = device_ops(run)
    if not loaded or not loaded[0] or not run["trace"].get("busy_s"):
        return None
    seconds = seconds_where(*loaded, wanted)
    return None if seconds is None else 100.0 * seconds / run["trace"]["busy_s"]


def scope_pct(run, scope):
    """Device time under ``scope`` over device busy time, in percent."""
    return share_pct(run, lambda name: in_scope(name, scope))


def slots_held_per_step(run):
    """Routed slots that reached an expert held here, per step, summed over
    the routed layers: the held share of the window's booked slots times the
    slots a step routes."""
    routed = _program.counter(run, "moe_slots_routed_total")
    held = _program.counter(run, "moe_slots_held_total")
    if not routed or held is None:
        return None
    cfg, traffic = run["config"], run["traffic"]
    per_step = (traffic["batch_per_chip"] * run["chips"] * traffic["seq_len"] * cfg["num_experts_per_tok"]
                * flops_moe_lm.layers(cfg)[1])
    return held / routed * per_step / run["chips"]
