"""Share of the routed slots (positions x experts per token, over the layers)
whose expert this chip holds, over the window (counters ``moe_slots_held_total`` /
``moe_slots_routed_total``): 12.5 for 16 of 128 under even routing, which the
family's calibration of the routers aims at."""

from benchmarks.layer_metrics import _program


def read(run):
    routed = _program.counter(run, "moe_slots_routed_total")
    held = _program.counter(run, "moe_slots_held_total")
    return None if not routed or held is None else 100.0 * held / routed
