"""The read-ahead controller (data/autotune.py) and the donation contract of
the compiled step and loop: the state is donated, a batch never is, so a batch
the feed still holds survives the dispatch."""

import re
import warnings

import numpy as np
import pytest

import jax
import optax

from tensorflowonspark_tpu import obs, parallel
from tensorflowonspark_tpu.train import SyncDataParallel


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


class TestDonationSafety:
    def test_unpacked_default_donates_state_not_batches(self):
        # the loop's contract, read off the lowered IR (the examples'
        # real-data path): donate=True marks the state's leaves and no
        # other — the batch-list donation was what kept the "Some donated
        # buffers were not usable: uint8[...]" warning alive
        strategy = _strategy()
        k = 4
        optimizer = optax.sgd(0.05)
        batches = [strategy.shard_batch(b) for b in _xy_batches(k)]
        state = strategy.create_state(_linear_init, optimizer, jax.random.PRNGKey(0))

        def donors(donate):
            loop = strategy.compile_train_loop(_linear_loss, optimizer, k, donate=donate)
            return loop.lower(state, batches).as_text().count("jax.buffer_donor")

        assert donors(False) == 0
        assert donors(True) == len(jax.tree.leaves(state))


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
    """The donation-warning pin, on the programs the benchmark's cells and the
    examples run: ``compile_train_loop`` and ``compile_train_step`` with a
    batch-stats ResNet loss over raw uint8 images + int labels (``mutable``),
    and ``compile_train_step`` with the language model's loss over int32
    tokens. "Some donated buffers were not usable: uint8[...], int32[...]"
    must stay dead. Pinned at the IR level — no uint8 image, int32 label or
    int32 token leaf may carry ``jax.buffer_donor`` — and at dispatch,
    re-feeding the same batch warning-free."""

    K = 4

    def _image_job(self, hw=8, b=8):
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
        rng = np.random.default_rng(0)
        host = [
            {"image": rng.integers(0, 256, (b, hw, hw, 3), dtype=np.uint8),
             "label": rng.integers(0, 10, b).astype(np.int32)}
            for _ in range(self.K)
        ]
        return strategy, state, loss_fn, optimizer, [strategy.shard_batch(x) for x in host]

    def _job(self, program):
        """``(state, run, fed, steps a call)`` of one of the three programs."""
        if program == "step_tokens":
            from tensorflowonspark_tpu.models import transformer

            strategy = _strategy()
            model = transformer.create_model(
                vocab_size=64, d_model=16, n_layers=1, n_heads=2, d_ff=32, max_seq_len=16, attention="plain")
            optimizer = optax.adamw(1e-3)
            state = strategy.create_state(transformer.make_init_fn(model, 8), optimizer, jax.random.PRNGKey(0))
            rng = np.random.default_rng(0)
            batch = {"tokens": rng.integers(1, 64, (8, 17)).astype(np.int32),
                     "segment_ids": np.ones((8, 17), np.int32),
                     "positions": np.tile(np.arange(17, dtype=np.int32), (8, 1))}
            step = strategy.compile_train_step(transformer.make_loss_fn(model), optimizer, has_aux=True)
            return state, step, strategy.shard_batch(batch), 1
        strategy, state, loss_fn, optimizer, placed = self._image_job()
        if program == "step_image":
            return state, strategy.compile_train_step(loss_fn, optimizer, mutable=True), placed[0], 1
        return state, strategy.compile_train_loop(loss_fn, optimizer, self.K, mutable=True), placed, self.K

    PROGRAMS = ["loop_image", "step_image", "step_tokens"]

    @pytest.mark.parametrize("program", PROGRAMS)
    def test_lowered_ir_never_marks_batch_leaves_as_donors(self, program):
        state, run, fed, _ = self._job(program)
        text = run.lower(state, fed).as_text()
        donors = re.findall(r"tensor<([^>]*)>[^,)]*jax\.buffer_donor", text)
        assert donors, "donation disappeared entirely — state must donate"
        for d in donors:
            # uint8 images lower as ...xui8, label and token arrays as ...xi32
            # (the state's scalar step is tensor<i32>: no 'x')
            assert "ui8" not in d and "xi32" not in d, donors

    @pytest.mark.parametrize("program", PROGRAMS)
    def test_double_dispatch_refeeding_the_window_is_warning_free(self, program):
        state, run, fed, steps = self._job(program)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for _ in range(2):  # the bench re-feeds live batches: no copies
                state, metrics = run(state, fed)
                jax.block_until_ready(metrics["loss"])
        bad = [str(w.message) for w in caught
               if "donated buffers" in str(w.message).lower()]
        assert bad == []
        assert int(jax.device_get(state.step)) == 2 * steps
