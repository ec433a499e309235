"""The child's first ``jax.devices()``: TPU runtime start-up."""


def read(run):
    return run["parts"].get("backend_init_s")
