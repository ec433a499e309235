"""Real-token share of the packed rows consumed in the window (a row's
``seq_len`` slots)."""


def read(run):
    counts = run["window"]["counts"]
    if not counts.get("rows"):
        return None
    return 100.0 * counts["real_tokens"] / (counts["rows"] * run["traffic"]["seq_len"])
