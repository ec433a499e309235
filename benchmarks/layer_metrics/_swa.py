"""What the ``swa_*`` readers share: the device time of the flash kernels by
the rule they ran under (``flash_fwd_win`` / ``flash_bwd_dkv_win`` in the
sliding layers, ``flash_fwd_seg`` / ``flash_bwd_dkv_seg`` in the full ones, as
``ops/flash_attention.py`` names them), a step's visible pairs under each
rule, the share of one counter in another and the slots that reached the
experts held here. A program without them reads None everywhere.

The cell runs one row a step and the rows differ widely (a lone document of
8192 has twice the visible pairs of two of 4000), so a roofline sets the
traced steps' kernel time against the pairs of the rows those steps ran
(``parts["traced_*"]``, which ``families/swa_lm.py`` keeps of the last
``trace_steps`` batches), not against the window's mean row."""

from benchmarks import flops, flops_swa_lm
from benchmarks.layer_metrics import _program

KERNELS = {True: ("flash_fwd_win", "flash_bwd_dkv_win"), False: ("flash_fwd_seg", "flash_bwd_dkv_seg")}


def flash_seconds(run, windowed):
    """Device seconds of one rule's two kernels in the traced window (mean
    over chips), or None where the trace names neither."""
    trace = run.get("trace")
    if not trace:
        return None
    found = [trace["kernel_s"][k] for k in KERNELS[windowed] if k in trace["kernel_s"]]
    return sum(found) if found else None


def flash_time_pct(run, windowed):
    seconds = flash_seconds(run, windowed)
    return None if seconds is None else 100.0 * seconds / run["trace"]["busy_s"]


def flash_roofline_pct(run, windowed):
    """One layer type's kernels against the least time one chip could take
    for the traced steps' attention in those layers, whatever implements it:
    the larger of the traced rows' visible pairs' operations / bf16 peak and
    the operands' bytes / HBM bandwidth, over the kernels' device time in
    the traced steps."""
    seconds = flash_seconds(run, windowed)
    parts, peak = run["parts"], run.get("peak")
    if seconds is None or not peak or not parts.get("traced_rows"):
        return None
    least, _bound = flops.roofline_seconds(
        flops_swa_lm.attention_flops(
            run["config"], parts["traced_pairs"], parts["traced_pairs_window"], windowed=windowed),
        flops_swa_lm.flash_bytes(run["config"], parts["traced_rows"], run["traffic"]["seq_len"], windowed), peak)
    return 100.0 * least / seconds


def counter_share_pct(run, part, whole):
    """One of the window's counters over another, in percent."""
    part, whole = _program.counter(run, part), _program.counter(run, whole)
    return None if part is None or not whole else 100.0 * part / whole


def slots_held_per_step(run):
    """Routed slots that reached an expert held here, per step and chip,
    summed over the routed layers: the held share of the window's booked
    slots times the slots a step routes."""
    routed = _program.counter(run, "moe_slots_routed_total")
    held = _program.counter(run, "moe_slots_held_total")
    if not routed or held is None:
        return None
    traffic = run["traffic"]
    return held / routed * flops_swa_lm.slots_per_step(run["config"], traffic["batch_per_chip"], traffic["seq_len"])
