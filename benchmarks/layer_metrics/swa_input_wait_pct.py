"""Share of the window the loop spent inside ``bench.next_batch``, waiting for
the input pipeline to hand over a batch (the queue's wait and the placement on
the device are both inside it)."""

from benchmarks.layer_metrics import _shared


def read(run):
    return _shared.window_pct(run, "bench.next_batch")
