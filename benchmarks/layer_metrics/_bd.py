"""What the ``bd_*`` readers share: the block-diffusion flash kernels' device
time (``flash_fwd_bd``, ``flash_bwd_dkv_bd``, as ``ops/flash_attention.py``
names them under its second rule) and the slots that reached the experts held
here. A program without them reads None everywhere."""

from benchmarks import flops_bd_lm
from benchmarks.layer_metrics import _program

FLASH_KERNELS = ("flash_fwd_bd", "flash_bwd_dkv_bd")


def flash_seconds(run):
    """Device seconds of the two mask kernels in the traced window (mean over
    chips), or None where the trace names neither."""
    trace = run.get("trace")
    if not trace:
        return None
    found = [trace["kernel_s"][k] for k in FLASH_KERNELS if k in trace["kernel_s"]]
    return sum(found) if found else None


def slots_held_per_step(run):
    """Routed slots that reached an expert held here, per step and chip,
    summed over the layers: the held share of the window's booked slots times
    the slots a step routes (both halves of every row)."""
    routed = _program.counter(run, "moe_slots_routed_total")
    held = _program.counter(run, "moe_slots_held_total")
    if not routed or held is None:
        return None
    traffic = run["traffic"]
    return held / routed * flops_bd_lm.slots_per_step(run["config"], traffic["batch_per_chip"], traffic["seq_len"])
