"""The first step to ``block_until_ready``: compile, or load from the
persistent cache, plus one step."""


def read(run):
    return run["parts"].get("first_step_s")
