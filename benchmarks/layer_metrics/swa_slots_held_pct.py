"""Share of the routed slots (positions x experts per token, over the routed
layers) whose expert this chip holds, over the window (counters
``moe_slots_held_total`` / ``moe_slots_routed_total``): 3.125 for 8 of 256 under even
routing, which the family's calibration of the routers aims at."""

from benchmarks.layer_metrics import _swa


def read(run):
    return _swa.counter_share_pct(run, "moe_slots_held_total", "moe_slots_routed_total")
