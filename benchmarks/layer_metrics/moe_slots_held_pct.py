"""Share of the routed slots (tokens x experts per token, over the routed
layers) whose expert this chip holds, over the window (counters
``moe_slots_held_total`` / ``moe_slots_routed_total``): 100 x held / router's
width under even routing, 12.5 for 8 of 64."""

from benchmarks.layer_metrics import _program


def read(run):
    routed = _program.counter(run, "moe_slots_routed_total")
    held = _program.counter(run, "moe_slots_held_total")
    return None if not routed or held is None else 100.0 * held / routed
