"""Real-token share of the packed rows consumed in the window (data tokens:
a row's ``seq_len`` slots, before the model doubles it)."""


def read(run):
    counts = run["window"]["counts"]
    if not counts.get("rows"):
        return None
    return 100.0 * counts["real_tokens"] / (counts["rows"] * run["traffic"]["seq_len"])
