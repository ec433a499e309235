"""Target tokens trained per second per chip over the whole window: steps
completed x global batch x sequence length / chips / seconds."""


def read(run):
    return run["window"]["rate_per_chip"]
