"""Test environment bootstrap.

Multi-chip sharding is validated on a virtual 8-device CPU mesh (SURVEY.md §4:
the reference tested "multi-node" on a 2-worker local standalone cluster; our
analogue is multi-process local executors + a virtual device mesh).

The suite never touches an accelerator: the env vars pin this process's
children to CPU, and the config-API call pins this process itself — pytest
plug-ins may have imported jax before this file runs, after which the env
var is no longer read (it works as long as no backend is initialized yet).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # for spawned jax child processes
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# keep XLA's CPU thread usage sane on small CI machines
os.environ.setdefault("XLA_CPU_MULTI_THREAD_EIGEN", "false")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
