"""Shared harness utilities for multi-process test worlds.

One place for the CPU-world bootstrap used by the test suite, so fixes to
world wiring (platform forcing, gloo selection, coordinator addressing)
cannot drift between copies.
"""

import os


def join_cpu_world(pid, num_procs, coord_port, local_devices=2):
    """Join a local multi-process jax.distributed world on CPU devices.

    Forces the CPU platform (see util.force_platform), builds the
    reservation-shaped :class:`~tensorflowonspark_tpu.TFSparkNode.TFNodeContext`
    for process ``pid`` of ``num_procs`` with a loopback coordinator, and
    initializes the distributed runtime (gloo collectives). Returns the ctx;
    after this call ``jax.device_count() == num_procs * local_devices``.
    """
    from tensorflowonspark_tpu import util
    from tensorflowonspark_tpu.TFSparkNode import TFNodeContext

    util.force_platform("cpu", num_cpu_devices=local_devices)
    ctx = TFNodeContext(
        executor_id=pid,
        job_name="worker",
        task_index=pid,
        cluster_spec={"worker": ["localhost"] * num_procs},
        defaultFS="file://",
        working_dir=os.getcwd(),
        coordinator_address="127.0.0.1:{}".format(coord_port),
        num_processes=num_procs,
        process_id=pid,
    )
    ctx.initialize_distributed()
    return ctx
