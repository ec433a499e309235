"""Run N independent single-node instances in parallel — no cluster, no
reservation server.

Capability-parity with /root/reference/tensorflowonspark/TFParallel.py
(Spark barrier execution for parallel single-node inference,
TFParallel.py:17-64): each executor gets a synthetic
:class:`~tensorflowonspark_tpu.TFSparkNode.TFNodeContext` (executor id from
the task's partition index, ``num_workers`` = parallelism, no manager/feed
plane) and runs the user function in a spawned jax child so libtpu's
process-owns-chips rule holds and chips free up when the task ends.
"""

import logging
import os
import traceback

from tensorflowonspark_tpu import TFSparkNode, tpu_info, util

logger = logging.getLogger(__name__)


class _ParallelTask:
    def __init__(self, fn, tf_args, num_executors, env=None):
        self.fn = fn
        self.tf_args = tf_args
        self.num_executors = num_executors
        self.env = dict(env or {})

    def __call__(self, iterator):
        executor_id = None
        for i in iterator:
            executor_id = i if not isinstance(i, (list, tuple)) else i[0]
        if executor_id is None:
            return []
        ctx = TFSparkNode.TFNodeContext(
            executor_id=executor_id,
            job_name="worker",
            task_index=executor_id,
            cluster_spec={"worker": ["localhost"] * self.num_executors},
            defaultFS="file://",
            working_dir=os.getcwd(),
        )

        # partition this host's chips across co-resident instances — the
        # reference placed workers on GPUs by local index (gpu_info.py:102)
        chip_ids = tpu_info.local_chip_share(
            *self._local_placement(executor_id), platform=self.env.get("JAX_PLATFORMS")
        )

        def _entry():
            try:
                os.environ.update(self.env)
                os.environ.update(
                    tpu_info.visibility_env(
                        chip_ids=chip_ids, platform=self.env.get("JAX_PLATFORMS")
                    )
                )
                if self.env.get("JAX_PLATFORMS"):
                    util.force_platform(
                        self.env["JAX_PLATFORMS"], self.env.get("TOS_NUM_CPU_DEVICES")
                    )
                util.place_compile_cache()
                self.fn(self.tf_args, ctx)
            except BaseException:
                logger.error("TFParallel fn failed:\n%s", traceback.format_exc())
                raise SystemExit(1)

        child = util.spawn_process(_entry, name="jax-parallel-{}".format(executor_id))
        child.start()
        child.join()
        if child.exitcode != 0:
            raise RuntimeError(
                "TFParallel instance {} failed (exit {})".format(executor_id, child.exitcode)
            )
        return [executor_id]

    def _local_placement(self, executor_id):
        """(host-local rank, instances on this host). Real Spark barrier mode
        exposes co-located tasks via BarrierTaskContext (the reference's
        placement source, TFParallel.py:42-45); the local backend runs every
        instance on one host, so there the global id IS the local rank."""
        try:
            from pyspark import BarrierTaskContext

            ctx = BarrierTaskContext.get()
            infos = ctx.getTaskInfos()
            import socket

            me = socket.gethostname()
            local = [
                i for i, t in enumerate(infos)
                if t.address.split(":")[0] in (me, "localhost", "127.0.0.1")
            ]
            return local.index(ctx.partitionId()), max(len(local), 1)
        except Exception:
            return executor_id, self.num_executors


def run(sc, map_fn, tf_args, num_executors, env=None):
    """Run ``map_fn(tf_args, ctx)`` as ``num_executors`` independent instances
    (reference TFParallel.run, TFParallel.py:17). Returns the executor ids
    that completed."""
    kwargs = {"pin_to_executors": True} if getattr(sc, "PIN_SUPPORTED", False) else {}
    rdd = sc.parallelize(range(num_executors), num_executors, **kwargs)
    if hasattr(rdd, "barrier"):  # real Spark: barrier execution mode
        rdd = rdd.barrier()
    return rdd.mapPartitions(_ParallelTask(map_fn, tf_args, num_executors, env)).collect()
