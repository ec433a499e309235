"""The comparison that decides ``correct``.

The program's first training steps (driven through the window's own call and
feed) against the plain reference following the same batches from the same
seeded weights. Numbers compared, each with a limit of its own from
``limits/<workload>.json`` (set from chip readings; PERF.md gives them):

* ``loss_gap``: the largest relative gap of a step's loss;
* ``grad_gap``: the first gradient as the optimizer got it, by the worst
  leaf: |program's norm - reference's norm| over the larger of the
  reference's norm of that leaf and of the median leaf;
* ``change_gap``: the same, for the norm of the parameters' change over the
  steps followed;
* ``dir_gap``: how far the first gradient points from the reference's,
  ``|g - g_ref| / |g_ref|`` over all leaves, from the two sides' seeded
  sketches (``sketch.py``): a lower precision moves a gradient across itself
  far more than it moves its norm.

A cell's limits file gives a limit for each of the four. A step that returns
its state unchanged reads a ``change_gap`` of 1 (every leaf's change is
missing) and a ``grad_gap`` and ``dir_gap`` of 1 (the optimizer saw nothing).
"""

import json
import os
import statistics

from benchmarks import sketch

NUMBERS = ("loss_gap", "grad_gap", "change_gap", "dir_gap")
LIMITS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "limits")


def worst_leaf_gap(got, want):
    """(gap, leaf) of the leaf whose norm is farthest from the reference's."""
    floor = statistics.median(want.values())
    worst = (0.0, None)
    for leaf, ref in want.items():
        if leaf not in got:
            return float("inf"), leaf
        gap = abs(got[leaf] - ref) / max(ref, floor)
        if not gap <= worst[0]:
            worst = (gap, leaf)
    return worst


def readings(program, reference):
    """The numbers compared, from the two sides' losses and leaf norms."""
    steps = len(reference["losses"])
    loss_gap = max(
        abs(p - r) / abs(r) for p, r in zip(program["losses"][:steps], reference["losses"])
    ) if len(program["losses"]) >= steps else float("inf")
    grad_gap, grad_leaf = worst_leaf_gap(program["first_grad"], reference["first_grad"])
    change_gap, change_leaf = worst_leaf_gap(program["param_change"], reference["param_change"])
    return {
        "loss_gap": loss_gap,
        "grad_gap": grad_gap,
        "change_gap": change_gap,
        "dir_gap": sketch.relative_distance(program["first_grad_sketch"], reference["first_grad_sketch"]),
        "worst_leaves": {"grad": grad_leaf, "change": change_leaf},
    }


def load_limits(workload):
    """The limits of one cell: a file of its own, so a later cell adds one."""
    with open(os.path.join(LIMITS, workload + ".json")) as f:
        return json.load(f)


def judge(read, limits):
    """``(correct, lines)``: each number beside its limit."""
    lines, correct = [], True
    for name in NUMBERS:
        ok = read[name] <= limits[name]
        correct &= ok
        lines.append("check {}: {:.6g} (limit <= {:g}) {}".format(
            name, read[name], limits[name], "ok" if ok else "FAILED"))
    return bool(correct), lines
