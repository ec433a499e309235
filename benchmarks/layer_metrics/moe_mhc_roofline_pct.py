"""The hyper-connections' share of their roofline: the least time one chip
could take to move what a step's residual paths must move
(``mhc_bytes.step_bytes``: the streams read and written by the two passes
forward, the recomputed two and the two backward) at the HBM's bandwidth,
over the device time per step under ``tos.mhc``. Memory bounds it: the maps'
products are 24 columns wide."""

from benchmarks import mhc_bytes
from benchmarks.layer_metrics import _moe


def read(run):
    loaded, peak = _moe.device_ops(run), run.get("peak")
    if not loaded or not loaded[0] or not peak or not run["config"].get("hc_mult"):
        return None
    seconds = _moe.seconds_where(*loaded, lambda name: _moe.in_scope(name, "tos.mhc"))
    if not seconds:
        return None
    traffic = run["traffic"]
    least = mhc_bytes.step_bytes(
        run["config"], traffic["batch_per_chip"], traffic["seq_len"], traffic["remat"]) / peak["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / run["trace"]["steps"])
