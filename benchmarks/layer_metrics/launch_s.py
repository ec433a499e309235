"""``run.py`` start to the jax child entering ``main_fun``: the local backend,
reservation, executor and child spawn (harness clock)."""


def read(run):
    return run["parts"].get("launch_s")
