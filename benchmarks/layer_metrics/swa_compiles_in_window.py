"""Backend compilations inside the measured window (jax.monitoring's
backend_compile_duration events); expected 0."""


def read(run):
    return run["window"]["compiles"]
