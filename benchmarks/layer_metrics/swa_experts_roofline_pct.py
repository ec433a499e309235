"""The grouped products' share of their roofline at the experts' width (1024): the
least time one chip could take for a step's routed experts, the larger of operations /
bf16 peak, by the slots that reached the experts held here, and the 8 held experts'
matrices plus the slots' rows / HBM bandwidth, over the device time per step of the
grouped products' kernels (``ragged-dot-*``, as XLA names them)."""

from benchmarks import flops, flops_swa_lm
from benchmarks.layer_metrics import _moe, _swa


def read(run):
    loaded, peak = _moe.device_ops(run), run.get("peak")
    slots = _swa.slots_held_per_step(run)
    if not loaded or not peak or not slots:
        return None
    seconds = _moe.seconds_where(*loaded, _moe.is_grouped_product)
    if not seconds:
        return None
    least, _bound = flops.roofline_seconds(
        flops_swa_lm.expert_flops(run["config"], slots), flops_swa_lm.expert_bytes(run["config"], slots), peak)
    return 100.0 * least / (seconds / run["trace"]["steps"])
