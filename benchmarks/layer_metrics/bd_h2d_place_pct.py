"""Share of the window the loop's thread spent inside the program's
``shard_batch`` (span ``h2d_place``), handing batches to the device."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.window_pct(run, "h2d_place_seconds_total")
