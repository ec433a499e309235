"""Tests for the local multi-process execution backend."""

import os
import time

import pytest

from tensorflowonspark_tpu.backends.local import LocalSparkContext, TaskError


@pytest.fixture(scope="module")
def sc():
    ctx = LocalSparkContext(num_executors=2, task_timeout=60)
    yield ctx
    ctx.stop()


def _square_partition(it):
    return [x * x for x in it]


def test_parallelize_collect(sc):
    rdd = sc.parallelize(range(10), 4)
    assert rdd.getNumPartitions() == 4
    assert sorted(rdd.collect()) == list(range(10))


def test_map_partitions_and_sum(sc):
    rdd = sc.parallelize(range(5), 2).mapPartitions(_square_partition)
    assert rdd.sum() == sum(x * x for x in range(5))


def test_map_and_count(sc):
    rdd = sc.parallelize(range(7), 2).map(lambda x: x + 1)
    assert rdd.count() == 7
    assert sorted(rdd.collect()) == list(range(1, 8))


def test_union_epochs(sc):
    rdd = sc.parallelize(range(3), 1)
    unioned = sc.union([rdd] * 3)
    assert unioned.getNumPartitions() == 3
    assert sorted(unioned.collect()) == sorted(list(range(3)) * 3)


def test_union_of_transformed_rdds(sc):
    """The epochs-via-union trick must work on an already-mapped RDD
    (TFCluster.train unions a user RDD that typically has map chains)."""
    rdd = sc.parallelize(range(3), 1).map(lambda x: x * 10)
    other = sc.parallelize(range(2), 1).mapPartitions(_square_partition)
    unioned = sc.union([rdd, rdd, other])
    assert sorted(unioned.collect()) == sorted([0, 10, 20] * 2 + [0, 1])


def test_error_propagates_with_remote_traceback(sc):
    def boom(it):
        raise ValueError("deliberate failure in task")

    with pytest.raises(TaskError, match="deliberate failure"):
        sc.parallelize(range(4), 2).mapPartitions(boom).collect()


def test_pinned_tasks_run_on_distinct_executors(sc):
    def report_executor(it):
        list(it)
        return [int(os.environ["TOS_LOCAL_EXECUTOR_ID"])]

    rdd = sc.parallelize(range(2), 2, pin_to_executors=True)
    eids = rdd.mapPartitions(report_executor).collect()
    assert sorted(eids) == [0, 1]


def test_executor_state_persists_across_tasks(sc):
    """One task writes a file in the executor CWD; a pinned follow-up task on
    the same executor sees it (the SPARK_REUSE_WORKER analogue)."""

    def write_marker(it):
        list(it)
        with open("marker.txt", "w") as f:
            f.write(os.environ["TOS_LOCAL_EXECUTOR_ID"])
        return [1]

    def read_marker(it):
        list(it)
        return [os.path.exists("marker.txt")]

    sc.parallelize(range(2), 2, pin_to_executors=True).mapPartitions(write_marker).collect()
    got = sc.parallelize(range(2), 2, pin_to_executors=True).mapPartitions(read_marker).collect()
    assert got == [True, True]


def test_concurrent_jobs(sc):
    """A blocking job on pinned slots must not starve a second job — executors
    pull shared-queue tasks as they free up."""
    import threading

    def slowish(it):
        time.sleep(0.3)
        return [sum(it)]

    results = {}

    def run(name, pin):
        rdd = sc.parallelize(range(4), 2, pin_to_executors=pin)
        results[name] = rdd.mapPartitions(slowish).sum()

    t1 = threading.Thread(target=run, args=("a", True))
    t2 = threading.Thread(target=run, args=("b", False))
    t1.start(), t2.start()
    t1.join(30), t2.join(30)
    assert results["a"] == results["b"] == sum(range(4))


def test_exit_does_not_hang_on_partitions_left_queued_by_a_failed_job(tmp_path):
    """A job that fails leaves its remaining partitions in the task queue;
    after ``stop()`` nobody reads them, and the interpreter's exit used to
    block forever flushing the queue's feeder thread into a full pipe (seen
    as a driver that never ended after a refused launch)."""
    import subprocess
    import sys

    script = tmp_path / "driver.py"
    script.write_text(
        "from tensorflowonspark_tpu.backends.local import LocalSparkContext, TaskError\n"
        "def boom(it):\n"
        "    raise RuntimeError('first task fails')\n"
        "if __name__ == '__main__':\n"
        "    sc = LocalSparkContext(num_executors=1)\n"
        "    big = [b'x' * 300_000] * 16\n"
        "    try:\n"
        "        sc.parallelize(big, 16).foreachPartition(boom)\n"
        "    except TaskError:\n"
        "        print('failed as expected')\n"
        "    finally:\n"
        "        sc.stop()\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=root),
    )
    assert "failed as expected" in out.stdout, out.stderr
    assert out.returncode == 0
