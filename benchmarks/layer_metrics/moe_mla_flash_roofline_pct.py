"""The flash kernels' share of their roofline at latent attention's widths (keys
192, values 128): the least time one chip could take for a step's attention —
the larger of needed operations / bf16 peak (causal within each segment, from
the consumed rows) and q, k, v, o, do, dq, dk, dv bytes / HBM bandwidth — over
the three kernels' device time per step."""

from benchmarks import flops, flops_moe_lm
from benchmarks.layer_metrics import _shared


def read(run):
    seconds = _shared.flash_seconds(run)
    counts, peak = run["window"]["counts"], run.get("peak")
    if seconds is None or not peak or not counts.get("rows"):
        return None
    rows = run["traffic"]["batch_per_chip"]
    pairs = counts["pairs"] / counts["rows"] * rows
    least, _bound = flops.roofline_seconds(
        flops_moe_lm.attention_flops(run["config"], pairs),
        flops_moe_lm.flash_bytes(run["config"], rows, run["traffic"]["seq_len"]), peak)
    return 100.0 * least / (seconds / run["trace"]["steps"])
