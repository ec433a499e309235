"""Adaptive device-feed autotuner (data/autotune.py + train PackedLoopCache):
link-estimator math, the bucket decision rule with hysteresis, byte-identical
delivery for ANY window trajectory, bounded recompiles, the donation-safety
contract of the packed loop, and deterministic adaptation under the
``data.device_link`` chaos site."""

import warnings

import numpy as np
import pytest

import jax
import optax

from tensorflowonspark_tpu import chaos, obs, parallel
from tensorflowonspark_tpu.data import FeedAutotuner, LinkEstimator, autotuned_prefetch
from tensorflowonspark_tpu.data.autotune import (
    batch_nbytes,
    bucket_decomposition,
)
from tensorflowonspark_tpu.data.loader import packed_place
from tensorflowonspark_tpu.train import PackedLoopCache, SyncDataParallel

FEED_METRICS = (
    "feed_link_bytes_per_sec",
    "feed_transfer_fixed_cost_seconds",
    "feed_window_size",
    "feed_recompiles_total",
    "feed_transfer_seconds_total",
)


def _strategy():
    return SyncDataParallel(parallel.build_mesh({"dp": 8}))


def _linear_init(rng):
    k1, _ = jax.random.split(rng)
    return {"w": jax.random.normal(k1, (2, 1)) * 0.01, "b": np.zeros((1,), np.float32)}


def _linear_loss(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return ((pred - batch["y"]) ** 2).mean()


def _xy_batches(n, rows=8):
    rng = np.random.default_rng(0)
    return [
        {
            "x": rng.standard_normal((rows, 2)).astype(np.float32),
            "y": rng.standard_normal((rows, 1)).astype(np.float32),
        }
        for _ in range(n)
    ]


class TestLinkEstimator:
    def test_first_observations_seed_directly(self):
        est = LinkEstimator(alpha=0.3)
        assert not est.ready and est.predict(100) is None
        est.observe_fixed(0.2)
        est.observe(10_000, 0.2 + 0.001)  # stream share: exactly 1 ms
        assert est.ready
        assert est.fixed_s == pytest.approx(0.2)
        assert est.bytes_per_sec == pytest.approx(10_000 / 0.001)
        assert est.predict(20_000) == pytest.approx(0.2 + 0.002)

    def test_ewma_blends_with_alpha(self):
        est = LinkEstimator(alpha=0.3)
        est.observe_fixed(0.2)
        est.observe_fixed(0.1)
        assert est.fixed_s == pytest.approx(0.7 * 0.2 + 0.3 * 0.1)

    def test_fast_transfer_drags_fixed_down(self):
        # a whole transfer faster than the fixed estimate disproves the
        # estimate: the model must recover from a probe that caught a spike
        est = LinkEstimator(alpha=0.3)
        est.observe_fixed(0.2)
        est.observe(1_000, 0.05)
        assert est.fixed_s == pytest.approx(0.7 * 0.2 + 0.3 * 0.05)
        # the whole observation fits inside the (clamped) fixed estimate: it
        # resolves no stream share, so it must NOT poison the bandwidth
        # estimate with a near-infinite sample
        assert est.bytes_per_sec is None and not est.ready

    def test_unresolvable_transfer_leaves_bandwidth_untouched(self):
        est = LinkEstimator(alpha=0.5)
        est.observe_fixed(0.010)
        est.observe(1 << 20, 0.015)  # 5 ms of stream: 1 MiB / 0.005
        bw = est.bytes_per_sec
        assert bw == pytest.approx((1 << 20) / 0.005)
        est.observe(1 << 20, 0.008)  # inside fixed cost: clamps fixed only
        assert est.fixed_s < 0.010
        assert est.bytes_per_sec == pytest.approx(bw)

    def test_fixed_share_decreases_with_bytes(self):
        est = LinkEstimator()
        est.observe_fixed(0.1)
        est.observe(1_000_000, 0.1 + 0.05)
        shares = [est.fixed_share(k * 1_000_000) for k in (1, 2, 4, 8)]
        assert shares == sorted(shares, reverse=True)
        assert shares[0] == pytest.approx(0.1 / 0.15)

    def test_rejects_bad_alpha_and_ignores_bad_samples(self):
        with pytest.raises(ValueError):
            LinkEstimator(alpha=0.0)
        est = LinkEstimator()
        est.observe(0, 1.0)
        est.observe(100, 0.0)
        assert not est.ready


class TestBucketDecomposition:
    def test_binary_decomposition_is_exact_with_unit_bucket(self):
        buckets = (1, 2, 4, 8, 16)
        assert bucket_decomposition(13, buckets) == [8, 4, 1]
        assert bucket_decomposition(16, buckets) == [16]
        for n in range(0, 40):
            sizes = bucket_decomposition(n, buckets)
            assert sum(sizes) == n
            assert all(s in buckets for s in sizes)

    def test_residue_below_smallest_bucket_is_dropped(self):
        assert bucket_decomposition(5, (2, 4)) == [4]


class TestFeedAutotunerDecisions:
    def _tuner(self, **kw):
        kw.setdefault("buckets", (1, 2, 4, 8))
        kw.setdefault("down_patience", 2)
        return FeedAutotuner(**kw)

    def _seed_for_k4(self, tuner, b=1_000_000):
        # fixed 0.02, stream 0.05/batch: share(4b) = .02/.22 <= 0.1 < share(2b)
        tuner.note_fixed_probe(0.02)
        tuner.note_transfer(b, 0.02 + 0.05)
        assert tuner.recommend(b) == 4
        return b

    def test_not_ready_recommends_smallest_bucket(self):
        tuner = self._tuner()
        assert tuner.recommend(1_000_000) == 1

    def test_first_decide_jumps_to_recommendation(self):
        tuner = self._tuner()
        b = self._seed_for_k4(tuner)
        assert tuner.decide(b) == (4, 2)

    def test_upward_move_is_immediate_one_bucket_per_decide(self):
        tuner = self._tuner(alpha=0.9)
        b = self._seed_for_k4(tuner)
        tuner.decide(b)
        for _ in range(4):  # latency spike: fixed cost jumps 20x
            tuner.note_fixed_probe(0.4)
        assert tuner.recommend(b) == 8
        assert tuner.decide(b)[0] == 8  # one bucket up, no patience needed

    def test_downward_move_waits_for_patience(self):
        tuner = self._tuner(alpha=0.9, down_patience=2)
        b = self._seed_for_k4(tuner)
        tuner.decide(b)
        for _ in range(4):  # link recovers: fixed cost collapses
            tuner.note_fixed_probe(0.0005)
            tuner.note_transfer(b, 0.0005 + 0.05)
        assert tuner.recommend(b) == 1
        assert tuner.decide(b)[0] == 4  # streak 1 of 2: hold
        assert tuner.decide(b)[0] == 2  # patience met: one bucket down
        assert tuner.decide(b)[0] == 2  # streak resets after a move
        assert tuner.decide(b)[0] == 1

    def test_depth_shrinks_for_deep_windows(self):
        tuner = self._tuner(deep_window_k=8)
        assert tuner.depth(2) == 2
        assert tuner.depth(8) == 1

    def test_rejects_bad_config(self):
        with pytest.raises(ValueError):
            FeedAutotuner(buckets=())
        with pytest.raises(ValueError):
            FeedAutotuner(buckets=(0, 2))
        with pytest.raises(ValueError):
            FeedAutotuner(overhead_target=1.5)

    def test_all_feed_metrics_registered_and_published(self):
        tuner = self._tuner()
        b = self._seed_for_k4(tuner)
        tuner.decide(b)
        snap = obs.snapshot()
        flat = dict(snap["gauges"])
        flat.update(snap["counters"])
        for name in FEED_METRICS:
            assert name in flat, name
        assert flat["feed_window_size"]["value"] == 4
        assert flat["feed_transfer_fixed_cost_seconds"]["value"] == pytest.approx(0.02)
        assert flat["feed_link_bytes_per_sec"]["value"] == pytest.approx(1_000_000 / 0.05)
        assert flat["feed_transfer_seconds_total"]["value"] > 0


class TestAutotunedPrefetchStream:
    """The delivery contract: byte-identical batch stream for ANY controller
    trajectory — windows in arrival order, the source tail flushed by binary
    decomposition, nothing dropped or duplicated."""

    def _delivered(self, host, strategy, **tuner_kw):
        out, ks = [], []
        tuner = FeedAutotuner(**tuner_kw)
        for w in autotuned_prefetch(iter(host), strategy, tuner=tuner):
            assert w.k in tuner.buckets
            ks.append(w.k)
            data = jax.device_get(w.data)
            for i in range(w.k):
                out.append({k: np.asarray(v)[i] for k, v in data.items()})
        return out, ks

    @pytest.mark.parametrize("n", [1, 7, 11, 16])
    def test_stream_identical_across_bucket_sets(self, n):
        strategy = _strategy()
        host = _xy_batches(n)
        base, base_ks = self._delivered(host, strategy, buckets=(1,))
        assert base_ks == [1] * n
        for buckets in [(1, 2), (1, 4), (1, 2, 4, 8, 16)]:
            got, ks = self._delivered(host, strategy, buckets=buckets)
            assert sum(ks) == n
            assert len(got) == n
            for a, b in zip(got, base):
                for key in ("x", "y"):
                    np.testing.assert_array_equal(a[key], b[key])

    def test_tuner_kwargs_construct_default_tuner(self):
        strategy = _strategy()
        host = _xy_batches(3)
        ws = list(autotuned_prefetch(iter(host), strategy, buckets=(1,)))
        assert [w.k for w in ws] == [1, 1, 1]

    def test_batch_nbytes_counts_all_leaves(self):
        b = _xy_batches(1)[0]
        assert batch_nbytes(b) == b["x"].nbytes + b["y"].nbytes


class TestPackedLoopCache:
    def test_compiles_at_most_once_per_bucket_and_counts(self):
        strategy = _strategy()
        optimizer = optax.sgd(0.05)
        cache = PackedLoopCache(strategy, _linear_loss, optimizer)
        before = obs.snapshot()["counters"]["feed_recompiles_total"]["value"]
        l2 = cache.loop_for(2)
        assert cache.loop_for(2) is l2
        cache.loop_for(4)
        assert cache.compiled_sizes == [2, 4]
        after = obs.snapshot()["counters"]["feed_recompiles_total"]["value"]
        assert after - before == 2

    def test_run_trains_through_autotuned_windows(self):
        strategy = _strategy()
        optimizer = optax.sgd(0.05)
        state = strategy.create_state(_linear_init, optimizer, jax.random.PRNGKey(0))
        cache = PackedLoopCache(strategy, _linear_loss, optimizer)
        n = 11
        for w in autotuned_prefetch(
            iter(_xy_batches(n)), strategy, buckets=(1, 2, 4)
        ):
            state, metrics = cache.run(state, w)
            jax.block_until_ready(metrics["loss"])
        # every batch trained exactly one step, whatever the windowing
        assert int(jax.device_get(state.step)) == n
        assert np.isfinite(float(jax.device_get(metrics["loss"])))


class TestDonationSafety:
    """The packed loop's donation contract (satellite of the autotuner: the
    prefetch buffer retains windows for double-buffering, so the default
    packed path must never donate them)."""

    def _compiled(self, strategy, k, donate):
        optimizer = optax.sgd(0.05)
        state = strategy.create_state(_linear_init, optimizer, jax.random.PRNGKey(0))
        loop = strategy.compile_train_loop(
            _linear_loss, optimizer, k, donate=donate, packed=True
        )
        return state, loop

    def test_packed_default_donation_emits_no_unusable_warning(self):
        strategy = _strategy()
        k = 4
        state, loop = self._compiled(strategy, k, donate=True)
        window = packed_place(_xy_batches(k), strategy)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for _ in range(2):  # window re-fed: it must survive the dispatch
                state, metrics = loop(state, window)
                jax.block_until_ready(metrics["loss"])
        donated = [w for w in caught if "donated buffers" in str(w.message).lower()]
        assert donated == [], [str(w.message) for w in donated]
        assert int(jax.device_get(state.step)) == 2 * k

    def test_packed_default_donates_state_not_batches(self):
        # the contract itself, read off the lowered IR: packed donate=True
        # means "state" — the [K,B,...] stack is NOT marked as a buffer
        # donor; donate="batches" forces it (and marks exactly the window's
        # leaves on top of the state's)
        strategy = _strategy()
        k = 4
        window = packed_place(_xy_batches(k), strategy)

        def donors(donate):
            state, loop = self._compiled(strategy, k, donate=donate)
            return loop.lower(state, window).as_text().count("jax.buffer_donor")

        default, state_only, forced = donors(True), donors("state"), donors("batches")
        assert donors(False) == 0
        assert default == state_only > 0
        n_window_leaves = len(jax.tree.leaves(window))
        assert forced == state_only + n_window_leaves

    def test_unpacked_default_donates_state_not_batches(self):
        # same contract for the NON-packed loop (the examples' real-data
        # path): donate=True marks state leaves only — the batch-list
        # donation was what kept the "Some donated buffers were not
        # usable: uint8[...]" warning alive in the bench tail
        strategy = _strategy()
        k = 4
        optimizer = optax.sgd(0.05)
        batches = [strategy.shard_batch(b) for b in _xy_batches(k)]

        def donors(donate):
            state = strategy.create_state(
                _linear_init, optimizer, jax.random.PRNGKey(0)
            )
            loop = strategy.compile_train_loop(
                _linear_loss, optimizer, k, donate=donate, packed=False
            )
            return loop.lower(state, batches).as_text().count("jax.buffer_donor")

        default, state_only, forced = donors(True), donors("state"), donors("batches")
        assert donors(False) == 0
        assert default == state_only > 0
        n_batch_leaves = len(jax.tree.leaves(batches))
        assert forced == state_only + n_batch_leaves


@pytest.mark.chaos
@pytest.mark.perf_smoke
class TestChaosDeviceLink:
    """Deterministic end-to-end adaptation: ``data.device_link`` injects a
    per-transfer delay INSIDE the autotuner's timed region, so injected
    latency flows straight into the link estimate. Sleep-staged like the
    other perf_smoke legs — the assertions are structural (which bucket the
    controller picked), never absolute throughput."""

    @pytest.fixture(autouse=True)
    def _clean_chaos(self):
        chaos.uninstall()
        yield
        chaos.uninstall()

    def _drain(self, host, strategy, tuner):
        """Run one stream through autotuned_prefetch; return (delivered
        per-batch host arrays, window sizes)."""
        out, ks = [], []
        for w in autotuned_prefetch(iter(host), strategy, tuner=tuner):
            ks.append(w.k)
            data = np.asarray(jax.device_get(w.data["x"]))
            out.extend(data[i] for i in range(w.k))
        return out, ks

    def test_latency_up_moves_k_up_then_recovery_moves_k_down(self):
        strategy = _strategy()
        # alpha/reprobe tuned for a short test: the estimator forgets the
        # spike within a few windows once the injected latency is gone
        tuner = FeedAutotuner(
            buckets=(1, 2, 4), alpha=0.7, reprobe_every=1, down_patience=1
        )

        # -- phase 1: 60 ms injected per-transfer latency dwarfs the real
        # CPU transfer time, so the fixed-cost share is ~1 at every bucket
        # and the controller must ratchet to the top bucket; 1 MiB batches
        # keep the window transfers long enough beyond the probes that the
        # bandwidth term resolves (sub-probe transfers feed only the
        # fixed-cost clamp)
        plan = chaos.ChaosPlan(seed=0).site("data.device_link", probability=1.0, delay_s=0.06)
        chaos.install(plan, propagate=False)
        spike = [{"x": np.full((8, 128, 256), i, np.float32)} for i in range(10)]
        got, ks = self._drain(spike, strategy, tuner)
        assert plan.fired("data.device_link") > 0
        assert max(ks) == 4
        assert tuner._k == 4
        assert sum(ks) == len(spike)
        for i, arr in enumerate(got):  # byte-identical delivery under chaos
            np.testing.assert_array_equal(arr, spike[i]["x"])

        # -- phase 2: latency gone; 8 MiB batches put the per-batch stream
        # time (~10 ms on any host) far above what a noisy sub-millisecond
        # probe can re-inflate the fixed estimate to, so once the spike
        # decays the recommendation falls and K must come back down — and
        # stay down through the end of the stream
        chaos.uninstall()
        calm = [{"x": np.full((8, 512, 512), i, np.float32)} for i in range(24)]
        got, ks = self._drain(calm, strategy, tuner)
        assert sum(ks) == len(calm)
        assert tuner._k < 4, ks
        assert ks[-1] < 4, ks
        for i, arr in enumerate(got):
            np.testing.assert_array_equal(arr, calm[i]["x"])


class TestReadaheadAutotuner:
    """The third controller: shard read-ahead depth steered by the same
    stall accounting ``classify_stalls`` reads — deepen only when the
    interval was io_bound, never when decode is the bottleneck."""

    def _tuner(self, **kw):
        from tensorflowonspark_tpu.data.autotune import ReadaheadAutotuner

        kw.setdefault("min_depth", 1)
        kw.setdefault("max_depth", 6)
        kw.setdefault("down_patience", 2)
        return ReadaheadAutotuner(**kw)

    def test_starved_and_io_bound_deepens_immediately(self):
        t = self._tuner()
        # consumer starved 40% of the interval, shard IO >= parse: deepen
        assert t.decide(2, read_delta=3.0, parse_delta=1.0, wait_delta=0.8,
                        elapsed=2.0) == 3

    def test_starved_but_decode_bound_is_not_its_problem(self):
        t = self._tuner()
        # same starvation but parse dominates IO: the decode autotuner's
        # territory — deepening read-ahead cannot fix it, depth holds
        assert t.decide(2, read_delta=1.0, parse_delta=3.0, wait_delta=0.8,
                        elapsed=2.0) == 2

    def test_idle_shrinks_only_after_down_patience(self):
        t = self._tuner(down_patience=2)
        assert t.decide(4, 0.1, 0.1, 0.0, 2.0) == 4  # streak 1 of 2: hold
        assert t.decide(4, 0.1, 0.1, 0.0, 2.0) == 3  # patience met
        assert t.decide(3, 0.1, 0.1, 0.0, 2.0) == 3  # streak reset by move

    def test_busy_interval_resets_the_down_streak(self):
        t = self._tuner(down_patience=2)
        assert t.decide(4, 0.1, 0.1, 0.0, 2.0) == 4   # idle: streak 1
        # a moderately-waiting interval (neither idle nor starved+io_bound)
        assert t.decide(4, 1.0, 3.0, 0.5, 2.0) == 4   # streak cleared
        assert t.decide(4, 0.1, 0.1, 0.0, 2.0) == 4   # idle again: streak 1

    def test_bounds_are_respected(self):
        t = self._tuner(min_depth=2, max_depth=3, down_patience=1)
        assert t.decide(3, 3.0, 1.0, 1.0, 2.0) == 3  # at max: no deeper
        assert t.decide(2, 0.0, 0.0, 0.0, 2.0) == 2  # at min: no shallower

    def test_zero_elapsed_is_a_noop(self):
        t = self._tuner()
        assert t.decide(2, 1.0, 0.0, 1.0, 0.0) == 2

    def test_rejects_inverted_bounds(self):
        from tensorflowonspark_tpu.data.autotune import ReadaheadAutotuner

        with pytest.raises(ValueError):
            ReadaheadAutotuner(min_depth=4, max_depth=2)

    def test_tick_gates_on_check_every_and_publishes_gauge(self):
        clock = iter([0.0, 1.0, 2.5, 5.0]).__next__
        reads = iter([
            (0.0, 0.0, 0.0),   # first tick: baseline only
            (3.0, 1.0, 1.0),   # io_bound + starved over 2.5 s
            (3.1, 1.1, 1.0),   # idle interval
        ]).__next__
        t = self._tuner(check_every=2.0, clock=clock, read_counters=reads)
        assert t.tick(2) is None        # t=0: baseline
        assert t.tick(2) is None        # t=1: interval not elapsed
        assert t.tick(2) == 3           # t=2.5: starved + io_bound
        assert obs.snapshot()["gauges"]["readahead_depth"]["value"] == 3
        assert t.tick(3) == 3           # t=5: idle, streak 1 of 2: hold

    def test_publish_seeds_the_gauge_before_first_interval(self):
        t = self._tuner()
        t.publish(5)
        assert obs.snapshot()["gauges"]["readahead_depth"]["value"] == 5

    def test_default_counter_source_reads_the_obs_registry(self):
        t = self._tuner(check_every=0.0, clock=iter([0.0, 1.0]).__next__)
        read_c = obs.counter("data_producer_read_seconds_total")
        wait_c = obs.counter("data_consumer_wait_seconds_total")
        assert t.tick(1) is None        # baseline snapshot of real counters
        read_c.inc(2.0)
        wait_c.inc(0.5)                 # 50% starved, io dominates parse
        assert t.tick(1) == 2


class TestBenchLoopDonationPin:
    """The donation-warning pin, on the bench's exact loop configuration
    (``compile_train_loop(loss_fn, optimizer, K, mutable=True,
    donate="state", packed=...)`` with a batch-stats ResNet loss over raw
    uint8 images + int labels): "Some donated buffers were not usable:
    uint8[...], int32[...]" must stay dead. Pinned at the IR level — no
    uint8 image stack or int32 label leaf may carry ``jax.buffer_donor`` —
    and at dispatch, re-feeding the same window warning-free."""

    K = 4

    def _bench_loop(self, packed, hw=8, b=8):
        from tensorflowonspark_tpu.data import imagenet
        from tensorflowonspark_tpu.models import resnet

        strategy = _strategy()
        model = resnet.ResNet(stage_sizes=(1,), filters=(8,), num_classes=10,
                              bottleneck=False, stem="cifar")
        optimizer = optax.sgd(0.1, momentum=0.9)
        state = strategy.create_state(
            resnet.make_init_fn(model, image_size=hw), optimizer,
            jax.random.PRNGKey(0))
        loss_fn = resnet.make_loss_fn(
            model, weight_decay=1e-4, normalize=imagenet.device_normalize)
        loop = strategy.compile_train_loop(
            loss_fn, optimizer, self.K, mutable=True, donate="state",
            packed=packed)
        rng = np.random.default_rng(0)
        host = [
            {"image": rng.integers(0, 256, (b, hw, hw, 3), dtype=np.uint8),
             "label": rng.integers(0, 10, b).astype(np.int32)}
            for _ in range(self.K)
        ]
        if packed:
            window = packed_place(host, strategy)
        else:
            window = [strategy.shard_batch(x) for x in host]
        return state, loop, window

    @pytest.mark.parametrize("packed", [True, False])
    def test_lowered_ir_never_marks_batch_leaves_as_donors(self, packed):
        import re

        state, loop, window = self._bench_loop(packed)
        text = loop.lower(state, window).as_text()
        donors = re.findall(r"tensor<([^>]*)>[^,)]*jax\.buffer_donor", text)
        assert donors, "donation disappeared entirely — state must donate"
        for d in donors:
            # uint8 image stacks lower as ...xui8, label vectors as ...xi32
            # (the state's scalar step is tensor<i32>: no 'x')
            assert "ui8" not in d and "xi32" not in d, donors

    @pytest.mark.parametrize("packed", [True, False])
    def test_double_dispatch_refeeding_the_window_is_warning_free(self, packed):
        state, loop, window = self._bench_loop(packed)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for _ in range(2):  # the bench re-feeds live windows: no copies
                state, metrics = loop(state, window)
                jax.block_until_ready(metrics["loss"])
        bad = [str(w.message) for w in caught
               if "donated buffers" in str(w.message).lower()]
        assert bad == []
        assert int(jax.device_get(state.step)) == 2 * self.K
