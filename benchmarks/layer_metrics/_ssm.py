"""What the ``ssm_*`` readers share: the device time of the selective scan's
two kernels (``ssm_scan_fwd`` / ``ssm_scan_bwd``, as ``ops/selective_scan.py``
names them) and of the flash kernels by the rule they ran under
(``flash_fwd_win`` / ``flash_bwd_dkv_win`` in the windowed layer,
``flash_fwd_seg`` / ``flash_bwd_dkv_seg`` in the full and the cross layer),
set against what ``scan_bytes.py`` and ``flops_ssm_lm.py`` say a step needs.
A program without the kernels reads None everywhere.

As in ``_swa.py`` the flash rooflines set the traced steps' kernel time
against the pairs of the rows those steps ran (``parts["traced_*"]``, which
``families/ssm_lm.py`` keeps of the last ``trace_steps`` batches): one row a
step, and rows differ two-fold."""

from benchmarks import flops, flops_ssm_lm, scan_bytes
from benchmarks.layer_metrics import _program

SCAN_KERNELS = ("ssm_scan_fwd", "ssm_scan_bwd")
FLASH_KERNELS = {"window": ("flash_fwd_win", "flash_bwd_dkv_win"), "full": ("flash_fwd_seg", "flash_bwd_dkv_seg")}
#: the layer kinds (``flops_ssm_lm.layer_kinds``) each pair of flash kernels serves
FLASH_LAYERS = {"window": ("window",), "full": ("full", "cross")}


def kernel_seconds(run, kernels):
    """Device seconds of ``kernels`` in the traced window (mean over chips),
    or None where the trace names none of them."""
    trace = run.get("trace")
    if not trace:
        return None
    found = [trace["kernel_s"][k] for k in kernels if k in trace["kernel_s"]]
    return sum(found) if found else None


def kernel_time_pct(run, kernels):
    seconds = kernel_seconds(run, kernels)
    return None if seconds is None else 100.0 * seconds / run["trace"]["busy_s"]


def scan_roofline_pct(run):
    """The least time one chip could take to move what a step's scans must
    move (``scan_bytes.step_bytes``) at the HBM's bandwidth, over the two
    kernels' device time per step. Bytes only: see ``scan_bytes.py``."""
    seconds, peak = kernel_seconds(run, SCAN_KERNELS), run.get("peak")
    if seconds is None or not peak or "mb_per_layer" not in run["config"]:
        return None
    traffic = run["traffic"]
    least = scan_bytes.step_bytes(run["config"], traffic["batch_per_chip"], traffic["seq_len"]) / peak["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / run["trace"]["steps"])


def flash_roofline_pct(run, rule):
    """One rule's two kernels against the least time one chip could take for
    the traced steps' attention maps in the layers they serve: the larger of
    the traced rows' visible pairs' operations / bf16 peak (two maps a pair,
    scores at 64 and values at 128) and the operands' bytes / HBM bandwidth,
    over the kernels' device time in the traced steps."""
    seconds = kernel_seconds(run, FLASH_KERNELS[rule])
    parts, peak = run["parts"], run.get("peak")
    if seconds is None or not peak or not parts.get("traced_rows") or "mb_per_layer" not in run["config"]:
        return None
    kinds = FLASH_LAYERS[rule]
    least, _bound = flops.roofline_seconds(
        flops_ssm_lm.attention_flops(run["config"], parts["traced_pairs"], parts["traced_pairs_window"], kinds),
        flops_ssm_lm.flash_bytes(run["config"], parts["traced_rows"], run["traffic"]["seq_len"], kinds), peak)
    return 100.0 * least / seconds


def restarts_per_row(run):
    """Positions at which the scans start anew, a row of the window's emitted
    rows (the text plane's ``ssm_scan_restarts_total`` over its
    ``ssm_scan_positions_total`` / ``seq_len``)."""
    restarts = _program.counter(run, "ssm_scan_restarts_total")
    positions = _program.counter(run, "ssm_scan_positions_total")
    if restarts is None or not positions:
        return None
    return restarts / (positions / run["traffic"]["seq_len"])
