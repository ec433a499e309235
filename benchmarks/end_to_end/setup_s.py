"""Process start of ``run.py`` to the first measured step: launch, backend,
corpus, cache fill, state, compile or cache load, the checked steps, warm-up."""


def read(run):
    return run["parts"]["setup_s"]
