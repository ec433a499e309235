"""What the ``ssd_*`` readers share: the device time of the Mamba-2 scan's two
kernels (``ssd_scan_fwd`` / ``ssd_scan_bwd``, as ``ops/ssd_scan.py`` names
them) set against what ``ssd_ops.py`` says a step's scans need, and the
grouped products of experts that are two matrices in a latent set against
``flops_ssd_lm.py``'s count by the slots that reached the experts held here. A
program without the kernels, or a configuration of another dialect, reads None
everywhere."""

from benchmarks import flops, flops_ssd_lm, ssd_ops
from benchmarks.layer_metrics import _moe, _program, _ssm

SCAN_KERNELS = ("ssd_scan_fwd", "ssd_scan_bwd")


def kernel_seconds(run):
    """Device seconds of the scan's two kernels in the traced window, or None
    where the trace names neither."""
    return _ssm.kernel_seconds(run, SCAN_KERNELS)


def scan_roofline_pct(run):
    """The least time one chip could take for a step's scans in chunked form,
    the larger of their products' operations / bf16 peak and their operands'
    and chunk states' bytes / HBM bandwidth (``ssd_ops.py``), over the two
    kernels' device time per step."""
    seconds, peak = kernel_seconds(run), run.get("peak")
    if seconds is None or not peak or "hybrid_override_pattern" not in run["config"]:
        return None
    traffic = run["traffic"]
    rows, seq = traffic["batch_per_chip"], traffic["seq_len"]
    least, _bound = flops.roofline_seconds(
        ssd_ops.step_flops(run["config"], rows, seq), ssd_ops.step_bytes(run["config"], rows, seq), peak)
    return 100.0 * least / (seconds / run["trace"]["steps"])


def slots_held_per_step(run):
    """Routed slots that reached an expert held here, per step and chip,
    summed over the expert blocks: the held share of the window's booked
    slots times the slots a step routes."""
    routed = _program.counter(run, "moe_slots_routed_total")
    held = _program.counter(run, "moe_slots_held_total")
    if not routed or held is None or "hybrid_override_pattern" not in run["config"]:
        return None
    traffic = run["traffic"]
    return held / routed * flops_ssd_lm.slots_per_step(run["config"], traffic["batch_per_chip"], traffic["seq_len"])


def experts_roofline_pct(run):
    """The grouped products' share of their roofline where an expert is two
    matrices in the latent (1024 -> 2688 -> 1024): the larger of operations /
    bf16 peak, by the slots that reached the experts held here, and the held
    experts' matrices plus the slots' rows / HBM bandwidth, over the device
    time per step of the grouped products' kernels (``ragged-dot-*``)."""
    loaded, peak, slots = _moe.device_ops(run), run.get("peak"), slots_held_per_step(run)
    if not loaded or not peak or not slots:
        return None
    seconds = _moe.seconds_where(*loaded, _moe.is_grouped_product)
    if not seconds:
        return None
    least, _bound = flops.roofline_seconds(
        flops_ssd_lm.expert_flops(run["config"], slots), flops_ssd_lm.expert_bytes(run["config"], slots), peak)
    return 100.0 * least / (seconds / run["trace"]["steps"])
