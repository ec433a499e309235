"""Integration: a 2-node cluster's metrics flow — child registries published
over the TFManager channel, feed tasks accumulated on the feeder lane, all
merged by ``TFCluster.metrics()`` into one cluster snapshot."""

import time

import pytest

from tensorflowonspark_tpu import TFCluster
from tensorflowonspark_tpu.TFCluster import InputMode
from tensorflowonspark_tpu.backends.local import LocalSparkContext

CPU_ENV = {"JAX_PLATFORMS": "cpu"}


@pytest.fixture
def sc():
    ctx = LocalSparkContext(num_executors=2, task_timeout=120)
    yield ctx
    ctx.stop()


def fn_square_feed_with_metric(args, ctx):
    # the jax child's process-global registry: published periodically by the
    # SnapshotPublisher the node runtime starts
    from tensorflowonspark_tpu import obs

    obs.counter("child_marks_total", help="one per node main_fun entry").inc()
    # a gauge and a fractional counter of the node's own, in the same registry
    obs.gauge("child_probe_seconds", help="a value each node sets").set(0.25)
    obs.counter("child_busy_seconds_total", help="seconds each node adds").inc(0.3)
    feed = ctx.get_data_feed(train_mode=False)
    while not feed.should_stop():
        batch = feed.next_batch(16)
        if batch:
            feed.batch_results([x * x for x in batch])


class TestClusterMetrics:
    def test_metrics_returns_merged_cluster_snapshot(self, sc):
        cluster = TFCluster.run(
            sc, fn_square_feed_with_metric, {}, num_executors=2,
            input_mode=InputMode.SPARK, master_node=None,
            env=CPU_ENV, jax_distributed=False, reservation_timeout=180,
        )
        try:
            results = cluster.inference(sc.parallelize(range(100), 4)).collect()
            assert sorted(results) == sorted(x * x for x in range(100))

            # the feeder lane is accumulated synchronously at task end, but the
            # child lane is published on an interval — poll until both nodes'
            # child registries have landed
            deadline = time.monotonic() + 60
            while True:
                snap = cluster.metrics()
                marks = snap["counters"].get("child_marks_total", {}).get("value", 0)
                if marks >= 2 or time.monotonic() > deadline:
                    break
                time.sleep(0.5)

            # cluster-level sums: one mark per node, every row fed + returned
            assert snap["counters"]["child_marks_total"]["value"] == 2
            assert snap["counters"]["feed_rows_total"]["value"] == 100
            assert snap["counters"]["inference_results_total"]["value"] == 100
            # driver registry rides along: the reservation server counted both
            # node registrations (process-global, so >= in case other tests ran)
            assert snap["counters"]["reservation_registrations_total"]["value"] >= 2
            # per-node detail survives the merge
            assert set(snap["nodes"]) == {"worker:0", "worker:1"}
            # each child timed its own start, where it happens (before main_fun)
            for node_snap in snap["nodes"].values():
                assert node_snap["gauges"]["node_import_jax_seconds"]["value"] > 0
                assert node_snap["gauges"]["node_backend_start_seconds"]["value"] > 0
                assert {"child_import_jax", "child_backend_start"} <= {e["span"] for e in node_snap["events"]}
            for node_snap in snap["nodes"].values():
                assert node_snap["counters"]["child_marks_total"]["value"] == 1
            # the node's own gauge and counter cross the channel into the
            # cluster view
            assert "child_probe_seconds" in snap["gauges"]
            assert "child_busy_seconds_total" in snap["counters"]
            # cross-node gauge semantic is SUM: two nodes x 0.25. Exact sums
            # are asserted on a driver-free snapshot — the driver registry is
            # process-global, and whatever an earlier test in this process
            # published would otherwise ride into the sum.
            nodrv = cluster.metrics(include_driver=False)
            assert nodrv["gauges"]["child_probe_seconds"]["value"] == pytest.approx(0.5)
            assert nodrv["counters"]["child_busy_seconds_total"]["value"] == pytest.approx(0.6)
            for node_snap in snap["nodes"].values():
                assert node_snap["gauges"]["child_probe_seconds"]["value"] == pytest.approx(0.25)
            # lifecycle spans crossed the channel as events
            assert any(e.get("span") == "inference_wave" for e in snap["events"])
            # snapshot is JSON-able end to end (the exporter contract)
            import json

            json.dumps(snap)
        finally:
            cluster.shutdown(timeout=120)

    def test_metrics_without_driver_registry(self, sc):
        cluster = TFCluster.run(
            sc, fn_square_feed_with_metric, {}, num_executors=2,
            input_mode=InputMode.SPARK, master_node=None,
            env=CPU_ENV, jax_distributed=False, reservation_timeout=180,
        )
        try:
            cluster.inference(sc.parallelize(range(20), 2)).collect()
            snap = cluster.metrics(include_driver=False)
            # node-side feed counters present; driver-only counters absent
            assert snap["counters"]["feed_rows_total"]["value"] == 20
            assert "reservation_registrations_total" not in snap["counters"]
        finally:
            cluster.shutdown(timeout=120)
