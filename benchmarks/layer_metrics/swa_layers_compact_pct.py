"""Share of the routed layers' runs, over the window, whose held slots fitted
the compact slot buffer (twice the even share of the slots: 5,120 of 81,920 rows
for 8 of 256 experts) and so ran on it; the rest fell back to all the rows
(counters ``moe_layers_compact_total`` / ``moe_layers_at_bound_total``). None on a
program without the counters."""

from benchmarks.layer_metrics import _program


def read(run):
    compact = _program.counter(run, "moe_layers_compact_total")
    at_bound = _program.counter(run, "moe_layers_at_bound_total")
    if compact is None or at_bound is None or not compact + at_bound:
        return None
    return 100.0 * compact / (compact + at_bound)
