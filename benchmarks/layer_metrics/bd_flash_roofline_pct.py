"""The block-diffusion flash kernels' share of their roofline: the least time one
chip could take for a step's attention, whatever implements it — the larger of
needed operations / bf16 peak (the pairs the mask shows in the consumed rows, at 32
query heads x 128; in the last layer the noised queries' alone) and q, o, do, dq
at the 32 query heads plus k, v, dk, dv at the 4 key/value heads / HBM bandwidth —
over the two kernels' device time per step."""

from benchmarks import flops, flops_bd_lm
from benchmarks.layer_metrics import _bd


def read(run):
    seconds = _bd.flash_seconds(run)
    counts, peak = run["window"]["counts"], run.get("peak")
    if seconds is None or not peak or not counts.get("rows"):
        return None
    rows = run["traffic"]["batch_per_chip"]
    pairs = counts["pairs"] / counts["rows"] * rows
    least, _bound = flops.roofline_seconds(
        flops_bd_lm.attention_flops(run["config"], pairs),
        flops_bd_lm.flash_bytes(run["config"], rows, run["traffic"]["seq_len"]), peak)
    return 100.0 * least / (seconds / run["trace"]["steps"])
