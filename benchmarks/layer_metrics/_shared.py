"""Arithmetic the per-layer readers share. A reader gets the run's record —
the window's counters, spans and counts, the reduced trace (traced runs), the
compiled step's memory, the set-up's parts, the cell's files, the peaks — and
returns its number, or None where the run holds nothing for it to read."""


def window_pct(run, span):
    return 100.0 * run["window"]["spans"].get(span, 0.0) / run["window"]["seconds"]


def step_device_ms(run):
    trace = run.get("trace")
    return None if not trace else 1000.0 * trace["busy_s"] / trace["steps"]


def device_idle_pct(run):
    trace = run.get("trace")
    return None if not trace else 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def mfu_pct(run):
    """Needed operations of the window's steps over what the chips could do
    in the window at their bf16 peak."""
    window, peak = run["window"], run.get("peak")
    if not peak or not window["steps"]:
        return None
    done = window["flops_per_step"] * window["steps"]
    return 100.0 * done / (window["seconds"] * run["chips"] * peak["bf16_flops_per_s"])


def step_hbm_gb(run):
    return run["step_memory"]["total_bytes"] / 1e9


FLASH_KERNELS = ("flash_fwd_seg", "flash_bwd_dq_seg", "flash_bwd_dkv_seg")


def flash_seconds(run):
    """Device seconds of the three segmented flash kernels in the traced
    window (mean over chips), or None where the trace names none of them."""
    trace = run.get("trace")
    if not trace:
        return None
    found = [trace["kernel_s"][k] for k in FLASH_KERNELS if k in trace["kernel_s"]]
    return sum(found) if found else None
