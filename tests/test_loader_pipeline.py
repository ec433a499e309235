"""Pipelined input path (data/loader.py): determinism across every
pipelining knob, bounded shuffle-buffer behaviour, stall metrics, recycled
zero-copy batch buffers, the multiprocess decode-plane mode (byte-identical
to the thread pool, caches/budget across the process boundary), chaos
``data.shard_read`` faults, and the structural IO/parse overlap proof
(``perf_smoke``)."""

import time

import numpy as np
import pytest

from tensorflowonspark_tpu import chaos, native_io, obs, tfrecord
from tensorflowonspark_tpu.data import ImagePipeline


def _counter(name):
    return obs.snapshot()["counters"].get(name, {}).get("value", 0)


def _parse(rec):
    v = int(rec)
    return np.full((4, 4, 1), v % 251, np.uint8), v


@pytest.fixture
def shards(tmp_path):
    """Three shards of 137 records each; labels are the global record index
    0..410, so a batch stream identifies records exactly."""
    paths, n = [], 0
    for s in range(3):
        p = str(tmp_path / "part-{:05d}".format(s))
        with tfrecord.TFRecordWriter(p) as w:
            for _ in range(137):
                w.write(str(n).encode())
                n += 1
        paths.append(p)
    return paths


def _stream(paths, **kw):
    kw.setdefault("batch_size", 8)
    kw.setdefault("seed", 3)
    kw.setdefault("epochs", 2)
    pipe = ImagePipeline(paths, _parse, **kw)
    return [(b["image"].tobytes(), b["label"].tobytes()) for b in pipe]


class TestDeterminism:
    def test_stream_invariant_to_pipelining_knobs(self, shards):
        """Same seed ⇒ byte-identical batches: read-ahead on/off, chunked vs
        bulk reads, 1 vs 8 parse threads — none may reorder the stream."""
        base = _stream(shards, readahead=0, chunk_records=0, num_threads=1)
        assert len(base) == 2 * (411 // 8)  # 2 epochs, remainder dropped
        variants = [
            dict(readahead=2, chunk_records=0, num_threads=1),
            dict(readahead=0, chunk_records=16, num_threads=1),
            dict(readahead=0, chunk_records=0, num_threads=8),
            dict(readahead=2, chunk_records=16, num_threads=8),
            dict(readahead=3, chunk_records=7, num_threads=8),
        ]
        for kw in variants:
            assert _stream(shards, **kw) == base, kw

    def test_python_codec_fallback_matches_native(self, shards, monkeypatch):
        base = _stream(shards, readahead=2, chunk_records=16)
        monkeypatch.setattr(native_io, "stream_available", lambda: False)
        assert _stream(shards, readahead=2, chunk_records=16) == base

    def test_caches_replay_identically(self, shards):
        # epoch 2 is served from memory (raw bytes / decoded arrays) but must
        # be byte-identical to the uncached stream
        base = _stream(shards, readahead=2, chunk_records=16)
        for mode in ("raw", "decoded"):
            assert _stream(shards, readahead=2, chunk_records=16, cache=mode) == base

    def test_cache_persists_across_iterations(self, shards):
        pipe = ImagePipeline(
            shards, _parse, batch_size=8, seed=3, epochs=1, cache="raw",
            readahead=2, chunk_records=16,
        )
        first = [(b["image"].tobytes(), b["label"].tobytes()) for b in pipe]
        assert len(pipe._raw_complete) == 3
        second = [(b["image"].tobytes(), b["label"].tobytes()) for b in pipe]
        assert second == first

    def test_seed_changes_the_stream(self, shards):
        assert _stream(shards, seed=1) != _stream(shards, seed=2)

    def test_loop_prefetch_delivery_invariant_to_window_and_threads(self, shards):
        """The windowed device feed composes with the pipeline without
        touching the record stream: whatever the window size and however many
        parse threads feed it, the delivered batches are the host stream's,
        in order, up to the last whole window."""
        import jax

        from tensorflowonspark_tpu import parallel
        from tensorflowonspark_tpu.data import loop_prefetch
        from tensorflowonspark_tpu.train import SyncDataParallel

        strategy = SyncDataParallel(parallel.build_mesh({"dp": 8}))

        def delivered(num_threads, num_steps):
            pipe = ImagePipeline(
                shards, _parse, batch_size=8, seed=3, epochs=1,
                num_threads=num_threads,
            )
            out = []
            for window in loop_prefetch(iter(pipe), strategy, num_steps=num_steps):
                assert len(window) == num_steps
                for batch in jax.device_get(window):
                    out.append((np.asarray(batch["image"]).tobytes(), np.asarray(batch["label"]).tobytes()))
            return out

        host = _stream(shards, epochs=1, num_threads=1)
        assert len(host) == 411 // 8
        for threads, num_steps in [(1, 1), (8, 1), (1, 2), (8, 2), (8, 4)]:
            whole = len(host) - len(host) % num_steps
            assert delivered(threads, num_steps) == host[:whole], (threads, num_steps)

    def test_invalid_cache_mode_rejected(self, shards):
        with pytest.raises(ValueError):
            ImagePipeline(shards, _parse, batch_size=8, cache="disk")


class TestShuffleBuffer:
    def _labels(self, paths, seed, **kw):
        kw.setdefault("batch_size", 8)
        kw.setdefault("epochs", 1)
        kw.setdefault("drop_remainder", False)
        pipe = ImagePipeline(paths, _parse, seed=seed, **kw)
        return [v for b in pipe for v in b["label"].tolist()]

    def test_bounded_displacement_and_multiset(self, tmp_path):
        # single shard: input order == label value, so displacement is exact
        p = str(tmp_path / "part-00000")
        with tfrecord.TFRecordWriter(p) as w:
            for i in range(200):
                w.write(str(i).encode())
        buffer = 32
        out = self._labels([p], seed=0, shuffle_buffer=buffer)
        assert sorted(out) == list(range(200))  # nothing lost or duplicated
        for j, v in enumerate(out):
            # a record cannot be emitted before it has entered the buffer:
            # by output position j only j + buffer inputs have been read, so
            # no record can jump ahead more than the buffer size (it CAN lag
            # arbitrarily — an unlucky record may survive draws to the end)
            assert v <= j + buffer - 1, (j, v)
        # the stream is actually shuffled, and differently per seed
        assert out != list(range(200))
        assert out[:16] != self._labels([p], seed=1, shuffle_buffer=buffer)[:16]

    def test_buffer_of_one_disables_record_shuffle(self, tmp_path):
        p = str(tmp_path / "part-00000")
        with tfrecord.TFRecordWriter(p) as w:
            for i in range(40):
                w.write(str(i).encode())
        out = self._labels([p], seed=0, shuffle_buffer=1)
        assert out == list(range(40))  # shard order shuffles; records don't

    def test_multi_shard_multiset(self, shards):
        out = self._labels(shards, seed=5, shuffle_buffer=64)
        assert sorted(out) == list(range(411))


class TestStallMetrics:
    def test_producer_and_consumer_counters_advance(self, shards):
        names = (
            "data_producer_read_seconds_total",
            "data_producer_parse_seconds_total",
            "data_producer_emit_seconds_total",
            "data_consumer_wait_seconds_total",
        )
        before = {n: _counter(n) for n in names}
        _stream(shards, readahead=2, chunk_records=16)
        snap = obs.snapshot()["counters"]
        for n in names:
            assert n in snap, n
        # IO and parse genuinely happened; emit/wait only accrue when a side
        # blocks, so they are merely monotone
        assert _counter("data_producer_read_seconds_total") > before[
            "data_producer_read_seconds_total"
        ]
        assert _counter("data_producer_parse_seconds_total") > before[
            "data_producer_parse_seconds_total"
        ]
        for n in names[2:]:
            assert _counter(n) >= before[n]


class TestRecycledBuffers:
    def test_recycled_stream_matches_when_copied(self, shards):
        base = _stream(shards, readahead=2, chunk_records=16)
        pipe = ImagePipeline(
            shards, _parse, batch_size=8, seed=3, epochs=2,
            readahead=2, chunk_records=16, recycle_buffers=True,
        )
        got = [(b["image"].copy().tobytes(), b["label"].copy().tobytes()) for b in pipe]
        assert got == base

    def test_buffers_actually_recycle(self, shards):
        pipe = ImagePipeline(
            shards, _parse, batch_size=8, seed=3, epochs=2,
            readahead=2, chunk_records=16, recycle_buffers=True,
            prefetch_batches=1,
        )
        ids, n_batches = set(), 0
        for b in pipe:
            ids.add(id(b["image"]))
            n_batches += 1
        # pool cap is prefetch_batches + 2: far fewer distinct buffers than
        # batches proves reuse (fresh np.empty per batch would churn ids)
        assert n_batches == 2 * (411 // 8)
        assert len(ids) <= 3


class TestDecodePlaneMode:
    """``decode_workers > 0``: the parse stage runs in worker processes
    writing into shared-memory slabs — the delivered stream must stay
    byte-identical to the thread pool's, across every pipelining knob, and
    the caches/budget/fallback contracts must hold either side of the
    process boundary."""

    def test_stream_invariant_across_decode_workers(self, shards):
        base = _stream(shards, readahead=0, chunk_records=0, num_threads=1)
        variants = [
            dict(decode_workers=1, readahead=0, chunk_records=0),
            dict(decode_workers=1, readahead=2, chunk_records=16),
            dict(decode_workers=4, readahead=0, chunk_records=0),
            dict(decode_workers=4, readahead=2, chunk_records=16),
            dict(decode_workers=4, readahead=3, chunk_records=7),
        ]
        for kw in variants:
            assert _stream(shards, **kw) == base, kw

    def test_env_knob_engages_the_plane(self, shards, monkeypatch):
        from tensorflowonspark_tpu import obs

        base = _stream(shards)
        monkeypatch.setenv("TOS_DECODE_WORKERS", "2")
        assert _stream(shards) == base
        # the plane ran: its gauge got registered (back at 0 after close)
        assert "decode_workers" in obs.snapshot()["gauges"]
        assert obs.snapshot()["gauges"]["decode_workers"]["value"] == 0

    def test_thread_fallback_when_plane_unavailable(self, shards, monkeypatch):
        from tensorflowonspark_tpu.data import decode_plane

        base = _stream(shards)
        monkeypatch.setattr(decode_plane, "available", lambda: False)
        assert _stream(shards, decode_workers=4) == base

    def test_decoded_cache_populated_from_process_workers(self, shards):
        # decoded pixels flow back through the slab (never pickle) into the
        # parent's cache; epoch 2 replays from it byte-identically
        base = _stream(shards, readahead=2, chunk_records=16)
        pipe = ImagePipeline(
            shards, _parse, batch_size=8, seed=3, epochs=2,
            readahead=2, chunk_records=16, cache="decoded", decode_workers=2,
        )
        got = [(b["image"].tobytes(), b["label"].tobytes()) for b in pipe]
        assert got == base
        assert len(pipe._decoded) == 411
        # replay is served from the parent-side cache, process mode again
        second = [(b["image"].tobytes(), b["label"].tobytes()) for b in pipe]
        assert second == got

    def test_recycled_slabs_match_when_copied(self, shards):
        base = _stream(shards, readahead=2, chunk_records=16)
        pipe = ImagePipeline(
            shards, _parse, batch_size=8, seed=3, epochs=2,
            readahead=2, chunk_records=16, recycle_buffers=True,
            decode_workers=2,
        )
        got = [(b["image"].copy().tobytes(), b["label"].copy().tobytes()) for b in pipe]
        assert got == base

    def test_max_bad_records_budget_spans_the_process_boundary(self, tmp_path):
        # the poisoned record fails INSIDE a worker; the budget and the
        # skip counter must behave exactly as in-thread (holes backfilled,
        # batches stay full-size)
        p = str(tmp_path / "part-00000")
        with tfrecord.TFRecordWriter(p) as w:
            for i in range(20):
                w.write(str(i).encode() if i != 7 else b"poison")

        def run(max_bad):
            pipe = ImagePipeline(
                [p], _parse, batch_size=4, seed=0, epochs=1, shuffle=False,
                max_bad_records=max_bad, decode_workers=2,
            )
            return [int(x) for b in pipe for x in b["label"]]

        before = _counter("data_records_skipped_total")
        assert run(1) == [i for i in range(20) if i != 7][:16]
        assert _counter("data_records_skipped_total") == before + 1
        with pytest.raises(Exception, match="poison"):
            run(0)

    def test_slab_metrics_registered(self, shards):
        from tensorflowonspark_tpu import obs

        _stream(shards, decode_workers=2, recycle_buffers=True)
        snap = obs.snapshot()
        assert "decode_slab_bytes" in snap["gauges"]
        assert "decode_worker_restarts_total" in snap["counters"]
        assert "decode_slab_wait_seconds_total" in snap["counters"]


class TestChaosShardRead:
    pytestmark = pytest.mark.chaos

    @pytest.fixture(autouse=True)
    def _clean_chaos(self):
        chaos.uninstall()
        yield
        chaos.uninstall()

    def test_error_faults_absorbed_by_retry(self, shards):
        # two injected IOErrors on shard open: SHARD_READ_RETRY (3 attempts)
        # absorbs both; the epoch completes with every record intact
        plan = chaos.ChaosPlan(seed=0).site(
            "data.shard_read", probability=1.0, max_count=2, error=True
        )
        chaos.install(plan, propagate=False)
        faults_before = _counter("chaos_fault_data_shard_read_total")
        pipe = ImagePipeline(
            shards, _parse, batch_size=8, seed=3, epochs=1,
            drop_remainder=False, readahead=2, chunk_records=16,
        )
        labels = sorted(v for b in pipe for v in b["label"].tolist())
        assert labels == list(range(411))
        assert plan.fired("data.shard_read") == 2
        assert _counter("chaos_fault_data_shard_read_total") - faults_before == 2

    def test_delay_faults_only_slow_the_stream(self, shards):
        base = _stream(shards, readahead=2, chunk_records=16)
        plan = chaos.ChaosPlan(seed=0).site(
            "data.shard_read", probability=1.0, max_count=3, delay_s=0.01
        )
        chaos.install(plan, propagate=False)
        assert _stream(shards, readahead=2, chunk_records=16) == base
        assert plan.fired("data.shard_read") == 3

    def test_exhausted_retry_surfaces_the_error(self, shards):
        # more consecutive faults than the retry budget: the IOError reaches
        # the consumer instead of hanging the pipeline
        plan = chaos.ChaosPlan(seed=0).site(
            "data.shard_read", probability=1.0, max_count=None, error=True
        )
        chaos.install(plan, propagate=False)
        pipe = ImagePipeline(
            shards, _parse, batch_size=8, seed=3, epochs=1, readahead=2,
        )
        with pytest.raises(IOError):
            list(pipe)


@pytest.mark.perf_smoke
class TestOverlapSmoke:
    """Structural proof that read-ahead overlaps IO with parse: both stages
    are sleep-dominated (chaos shard-open delay, sleepy parse_fn), so wall
    time below the serial sum can only come from genuine overlap — no
    absolute-throughput assertion to flake on a loaded box."""

    @pytest.fixture(autouse=True)
    def _clean_chaos(self):
        chaos.uninstall()
        yield
        chaos.uninstall()

    def test_readahead_overlaps_io_and_parse(self, tmp_path):
        paths = []
        for s in range(4):
            p = str(tmp_path / "part-{:05d}".format(s))
            with tfrecord.TFRecordWriter(p) as w:
                for i in range(12):
                    w.write(str(s * 12 + i).encode())
            paths.append(p)

        def sleepy_parse(rec):
            time.sleep(0.005)
            v = int(rec)
            return np.full((2, 2, 1), v % 251, np.uint8), v

        chaos.install(
            chaos.ChaosPlan(seed=0).site(
                "data.shard_read", probability=1.0, delay_s=0.1
            ),
            propagate=False,
        )
        read_before = _counter("data_producer_read_seconds_total")
        parse_before = _counter("data_producer_parse_seconds_total")
        t0 = time.monotonic()
        pipe = ImagePipeline(
            paths, sleepy_parse, batch_size=4, shuffle=False, epochs=1,
            num_threads=1, readahead=2, chunk_records=4,
        )
        n_batches = sum(1 for _ in pipe)
        wall = time.monotonic() - t0
        read_s = _counter("data_producer_read_seconds_total") - read_before
        parse_s = _counter("data_producer_parse_seconds_total") - parse_before

        assert n_batches == 12
        # both stages really slept: 4 shard opens x 0.1s, 48 records x 5ms
        assert read_s > 0.3, read_s
        assert parse_s > 0.2, parse_s
        # the pipelining claim itself: wall beats the serial sum
        assert wall < 0.9 * (read_s + parse_s), (wall, read_s, parse_s)


def _make_jpeg_parse():
    from tensorflowonspark_tpu.data import imagenet

    return imagenet.make_parse_fn(True, image_size=16, seed=5, raw_uint8=True)


@pytest.fixture
def jpeg_shards(tmp_path):
    """Two shards of real JPEG Examples (labels = global index 0..59), the
    decode-mode matrix's substrate: every decode path must produce the same
    pixels from these bytes."""
    from tensorflowonspark_tpu.data import imagenet

    rng = np.random.default_rng(0)
    paths, n = [], 0
    for s in range(2):
        p = str(tmp_path / "img-{:05d}".format(s))
        with tfrecord.TFRecordWriter(p) as w:
            for _ in range(30):
                img = rng.integers(
                    0, 256, (24 + n % 5, 24 + n % 3, 3), dtype=np.uint8
                )
                w.write(imagenet.encode_example(img, n))
                n += 1
        paths.append(p)
    return paths


def _jstream(paths, slab_cache_dir=None, **kw):
    kw.setdefault("batch_size", 4)
    kw.setdefault("seed", 3)
    kw.setdefault("epochs", 1)
    kw.setdefault("readahead", 2)
    kw.setdefault("chunk_records", 16)
    pipe = ImagePipeline(
        paths, _make_jpeg_parse(), slab_cache_dir=slab_cache_dir, **kw
    )
    return [(b["image"].tobytes(), b["label"].tobytes()) for b in pipe]


class TestNativeDecodeAndSlabCache:
    """The byte-identical-stream contract across decode implementations:
    PIL threads, native threads, native worker processes, and the
    cross-epoch decoded-slab cache must all deliver the same batches — and
    charge a corrupt JPEG against ``max_bad_records`` identically."""

    def test_stream_invariant_across_decode_modes(self, jpeg_shards, tmp_path, monkeypatch):
        from tensorflowonspark_tpu.data import decode_plane

        base = _jstream(jpeg_shards)  # thread pool, native when available
        if native_io.jpg_available():
            native = _counter("decode_native_total")
            assert _jstream(jpeg_shards) == base
            assert _counter("decode_native_total") > native
        # PIL-forced threads
        monkeypatch.setenv(native_io.DECODE_ENV_VAR, "0")
        assert _jstream(jpeg_shards) == base
        monkeypatch.delenv(native_io.DECODE_ENV_VAR)
        # worker processes (native inside the workers)
        if decode_plane.available():
            assert _jstream(jpeg_shards, decode_workers=2) == base
        # cold cache, then a warm run served from committed generations
        cache = str(tmp_path / "slab-cache")
        assert _jstream(jpeg_shards, slab_cache_dir=cache) == base
        hits = _counter("decode_cache_hits_total")
        assert _jstream(jpeg_shards, slab_cache_dir=cache) == base
        # 59 of 60: the bootstrap record is decoded parent-side to learn
        # the slab geometry BEFORE the cache can open (it needs the shape)
        assert _counter("decode_cache_hits_total") - hits == 59
        # and a warm PROCESS run: hits lease slots without touching a worker
        if decode_plane.available():
            assert _jstream(jpeg_shards, slab_cache_dir=cache, decode_workers=2) == base

    def test_epoch_two_is_served_from_the_cache(self, jpeg_shards, tmp_path):
        cache = str(tmp_path / "slab-cache")
        base = _jstream(jpeg_shards, epochs=2)
        hits = _counter("decode_cache_hits_total")
        assert _jstream(jpeg_shards, epochs=2, slab_cache_dir=cache) == base
        # epoch 1 decoded and committed; epoch 2 hit for every record
        assert _counter("decode_cache_hits_total") - hits == 60
        assert obs.snapshot()["gauges"]["decode_cache_bytes"]["value"] > 0

    def test_cache_survives_pipeline_objects(self, jpeg_shards, tmp_path):
        # the elastic-relaunch shape: a NEW pipeline (fresh process in real
        # life) over the same shards + params adopts the committed
        # generations and skips decode entirely
        cache = str(tmp_path / "slab-cache")
        base = _jstream(jpeg_shards, slab_cache_dir=cache)
        hits = _counter("decode_cache_hits_total")
        native = _counter("decode_native_total")
        assert _jstream(jpeg_shards, slab_cache_dir=cache) == base
        assert _counter("decode_cache_hits_total") - hits == 59  # 60 - bootstrap
        assert _counter("decode_native_total") == native  # no native decode at all

    def test_cache_is_scoped_by_decode_params(self, jpeg_shards, tmp_path):
        from tensorflowonspark_tpu.data import imagenet

        cache = str(tmp_path / "slab-cache")
        _jstream(jpeg_shards, slab_cache_dir=cache)
        hits = _counter("decode_cache_hits_total")
        # a different augmentation seed is a different cache_key: the
        # committed generation must NOT serve it
        parse = imagenet.make_parse_fn(True, image_size=16, seed=6, raw_uint8=True)
        pipe = ImagePipeline(
            jpeg_shards, parse, batch_size=4, seed=3, epochs=1,
            slab_cache_dir=cache,
        )
        for _ in pipe:
            pass
        assert _counter("decode_cache_hits_total") == hits

    def test_env_knob_engages_the_cache(self, jpeg_shards, tmp_path, monkeypatch):
        base = _jstream(jpeg_shards)
        monkeypatch.setenv("TOS_SLAB_CACHE_DIR", str(tmp_path / "env-cache"))
        assert _jstream(jpeg_shards) == base
        hits = _counter("decode_cache_hits_total")
        assert _jstream(jpeg_shards) == base
        assert _counter("decode_cache_hits_total") - hits == 59  # 60 - bootstrap

    def test_corrupt_jpeg_charged_identically_in_all_modes(self, tmp_path, monkeypatch):
        from tensorflowonspark_tpu import tfrecord as tfr
        from tensorflowonspark_tpu.data import decode_plane, imagenet

        rng = np.random.default_rng(1)
        p = str(tmp_path / "poisoned-00000")
        with tfrecord.TFRecordWriter(p) as w:
            for i in range(12):
                if i == 7:  # valid Example, garbage JPEG bytes (last
                    # slot of round 2, so the backfill keeps label order)
                    w.write(tfr.encode_example({
                        "image/encoded": [b"\xff\xd8 not a jpeg"],
                        "image/class/label": [7],
                    }))
                else:
                    img = rng.integers(0, 256, (24, 24, 3), dtype=np.uint8)
                    w.write(imagenet.encode_example(img, i))

        def labels(max_bad, **kw):
            pipe = ImagePipeline(
                [p], _make_jpeg_parse(), batch_size=4, seed=0, epochs=1,
                shuffle=False, max_bad_records=max_bad, **kw)
            return [int(x) for b in pipe for x in b["label"]]

        good = [i for i in range(12) if i != 7][:8]
        modes = [dict(), dict(slab_cache_dir=str(tmp_path / "c"))]
        if decode_plane.available():
            modes.append(dict(decode_workers=2))
        for kw in modes:
            before = _counter("data_records_skipped_total")
            assert labels(1, **kw) == good, kw
            assert _counter("data_records_skipped_total") == before + 1, kw
            with pytest.raises(Exception):
                labels(0, **kw)
        # and PIL-forced threads charge the same record
        monkeypatch.setenv(native_io.DECODE_ENV_VAR, "0")
        before = _counter("data_records_skipped_total")
        assert labels(1) == good
        assert _counter("data_records_skipped_total") == before + 1

    def test_readahead_auto_stream_is_identical(self, jpeg_shards):
        base = _jstream(jpeg_shards)
        assert _jstream(jpeg_shards, readahead="auto") == base
        assert "readahead_depth" in obs.snapshot()["gauges"]


class TestChaosCacheAndReadahead:
    pytestmark = pytest.mark.chaos

    @pytest.fixture(autouse=True)
    def _clean_chaos(self):
        chaos.uninstall()
        yield
        chaos.uninstall()

    def test_cache_tear_is_rejected_and_stream_survives(self, jpeg_shards, tmp_path):
        # a torn commit (crash between manifest write and fsync) must be
        # rejected by verify-on-publish — the records decode again, the
        # stream never sees garbage
        base = _jstream(jpeg_shards, epochs=2)
        cache = str(tmp_path / "slab-cache")
        plan = chaos.ChaosPlan(seed=0).site(
            "data.cache_tear", probability=1.0, max_count=1
        )
        chaos.install(plan, propagate=False)
        rejects = _counter("decode_cache_rejects_total")
        hits = _counter("decode_cache_hits_total")
        assert _jstream(jpeg_shards, epochs=2, slab_cache_dir=cache) == base
        assert plan.fired("data.cache_tear") == 1
        assert _counter("decode_cache_rejects_total") - rejects == 1
        # epoch 1's torn generation served nothing: epoch 2 re-decoded
        assert _counter("decode_cache_hits_total") == hits
        # the epoch-2 commit was past the chaos budget: a fresh run hits
        chaos.uninstall()
        assert _jstream(jpeg_shards, slab_cache_dir=cache) == base[: len(base) // 2]
        assert _counter("decode_cache_hits_total") - hits == 59  # 60 - bootstrap

    def test_readahead_stall_only_slows_the_stream(self, jpeg_shards):
        base = _jstream(jpeg_shards)
        plan = chaos.ChaosPlan(seed=0).site(
            "data.readahead_stall", probability=1.0, max_count=3, delay_s=0.01
        )
        chaos.install(plan, propagate=False)
        read_before = _counter("data_producer_read_seconds_total")
        assert _jstream(jpeg_shards, readahead="auto") == base
        assert plan.fired("data.readahead_stall") == 3
        # the stall is charged to shard-read time, where the readahead
        # autotuner and classify_stalls can see it
        assert _counter("data_producer_read_seconds_total") - read_before >= 0.03


class _CountingStrategy:
    """Stands in for the strategy: placing a batch only counts it."""

    def __init__(self):
        self.placed = 0

    def shard_batch(self, batch):
        self.placed += 1
        return ("placed", batch)


@pytest.mark.parametrize(
    "n_batches,depth", [(6, 2), (3, 3), (1, 4)],
    ids=["steady", "source-as-long-as-depth", "source-shorter-than-depth"],
)
def test_device_prefetch_places_ahead_in_order(n_batches, depth):
    """The image cell's feed (``device_prefetch``): every batch comes out
    once, in the source's order, placed by the strategy; when the consumer
    holds batch i the next ``depth`` are already placed (as far as the source
    reaches), and a source shorter than ``depth`` drains without error."""
    from tensorflowonspark_tpu.data import device_prefetch

    strategy = _CountingStrategy()
    got = []
    for i, out in enumerate(device_prefetch(iter(range(n_batches)), strategy, depth=depth)):
        got.append(out)
        assert strategy.placed == min(i + 1 + depth, n_batches)
    assert got == [("placed", i) for i in range(n_batches)]
    assert strategy.placed == n_batches
