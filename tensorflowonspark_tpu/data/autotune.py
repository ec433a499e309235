"""The shard read-ahead controller: how many shards the reader streams ahead.

:class:`ReadaheadAutotuner` sizes the read-ahead of
``ImagePipeline(readahead="auto")`` from the loader's own stall counters —
deeper at once when the consumer starves on shard IO, shallower after a run
of idle intervals — and, handed another gauge, the store plane's prefetch
stager (:mod:`~tensorflowonspark_tpu.store.staging`). It is built from
:mod:`~tensorflowonspark_tpu.control`'s shared core, as the decode plane's
:class:`~tensorflowonspark_tpu.data.decode_plane.DecodeAutotuner` is, and
publishes its choice on the ``readahead_depth`` gauge, which surfaces in
``TFCluster.metrics()``.
"""

from tensorflowonspark_tpu import obs
from tensorflowonspark_tpu.control import Controller, DeltaTicker, StallRule

#: default upper bound for the stall-steered shard read-ahead depth
#: (``ImagePipeline(readahead="auto")``): deep enough to hide a slow remote
#: store behind decode, small enough that chunk queues stay bounded
DEFAULT_MAX_READAHEAD = 8


class ReadaheadAutotuner:
    """Self-sizing controller for the shard read-ahead depth.

    :class:`~tensorflowonspark_tpu.data.decode_plane.DecodeAutotuner`
    sizes the decode worker pool, and this one sizes how many shards the
    reader executor streams ahead of the parse stage — the knob that
    matters when the stall classification says **io_bound** (remote stores:
    gcsfuse, NFS, object stores with high per-read latency).

    Decision rule per interval of ``check_every`` seconds, from the deltas
    of the producer/consumer stall counters (the same accounting
    ``bench.classify_stalls`` reads):

    * consumer starved for more than ``starve_ratio`` of the interval AND
      shard IO dominated the parse stage (``read_delta >= parse_delta`` —
      the interval was io_bound, not decode_bound) → **deepen read-ahead
      one shard immediately**. Starvation whose cause is decode is left to
      the decode autotuner; deepening read-ahead cannot fix it.
    * consumer essentially never starved (wait share below ``idle_ratio``)
      → **shallow by one after ``down_patience`` consecutive idle
      intervals** (hysteresis), releasing reader threads and chunk-queue
      memory the pipeline demonstrably does not need.

    Bounds ``[min_depth, max_depth]``. Counter reads and the clock are
    injectable so the decision core is a pure function in tests, exactly
    like the decode autotuner. Publishes the chosen depth on the
    ``readahead_depth`` gauge.
    """

    def __init__(
        self,
        min_depth=1,
        max_depth=DEFAULT_MAX_READAHEAD,
        starve_ratio=0.05,
        idle_ratio=0.01,
        down_patience=2,
        check_every=2.0,
        clock=None,
        read_counters=None,
        gauge=None,
    ):
        self.min_depth = max(1, int(min_depth))
        self.max_depth = int(max_depth)
        if self.max_depth < self.min_depth:
            raise ValueError("max_depth must be >= min_depth")
        self.starve_ratio = float(starve_ratio)
        self.idle_ratio = float(idle_ratio)
        self.down_patience = max(1, int(down_patience))
        self.check_every = float(check_every)
        # the shared control core: starvation verdict, up-fast/down-slow
        # hysteresis inside the depth bounds, and the clocked delta gate
        self._rule = StallRule(
            starve_ratio=self.starve_ratio, idle_ratio=self.idle_ratio
        )
        self._ctl = Controller(
            lo=self.min_depth, hi=self.max_depth,
            down_patience=self.down_patience, name="readahead",
        )
        self._ticker = DeltaTicker(
            self.check_every, read_counters or self._read_obs, clock=clock
        )
        # the depth gauge is injectable so other read-ahead-shaped planes
        # (the store prefetch stager) can reuse the whole controller while
        # publishing on their own metric name
        self._depth_g = gauge if gauge is not None else obs.gauge(
            "readahead_depth", help="shard read-ahead depth currently allowed"
        )

    @staticmethod
    def _read_obs():
        counters = obs.snapshot()["counters"]

        def _c(counter_name):
            return counters.get(counter_name, {}).get("value", 0.0)

        return (
            _c("data_producer_read_seconds_total"),
            _c("data_producer_parse_seconds_total"),
            _c("data_consumer_wait_seconds_total"),
        )

    def publish(self, depth):
        """Publish ``depth`` on the ``readahead_depth`` gauge (the loader
        calls this once at startup so the gauge exists before the first
        interval elapses)."""
        self._depth_g.set(int(depth))

    def decide(self, depth, read_delta, parse_delta, wait_delta, elapsed):
        """Pure decision: the read-ahead depth argued for by one interval's
        counter deltas (no clock, no obs — the unit-testable core)."""
        if elapsed <= 0:
            return depth
        want = self._rule.want(wait_delta / elapsed, read_delta >= parse_delta)
        return self._ctl.step(depth, want)

    def tick(self, depth):
        """Clocked wrapper for :meth:`decide`: reads the counters at most
        every ``check_every`` seconds; returns the new target depth, or
        None when the interval has not elapsed yet."""
        out = self._ticker.tick()
        if out is None:
            return None
        (read_delta, parse_delta, wait_delta), elapsed = out
        target = self.decide(depth, read_delta, parse_delta, wait_delta, elapsed)
        if target != depth:
            self._depth_g.set(int(target))
        return target
