"""Images trained per second per chip over the whole window."""


def read(run):
    return run["window"]["rate_per_chip"]
