"""Collective time during which no compute runs on that device, over the
traced window (mean over chips). Only a cell on several chips has any."""


def read(run):
    trace = run.get("trace")
    if not trace or run["chips"] < 2:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
