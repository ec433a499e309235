"""Multiprocess decode plane (data/decode_plane.py): slab segments, the
slot lease protocol (fills, worker-side failures, respawn with no lost or
duplicated slots), pool resize/teardown hygiene, the worker-count
autotuner's decision rule, and the GIL-release proof (``perf_smoke``: the
parse runs in the workers' own processes and fills the thread pool's stream)."""

import collections
import functools
import glob
import os
import signal
import time

import numpy as np
import pytest

from tensorflowonspark_tpu import obs, shm
from tensorflowonspark_tpu.data import decode_plane
from tensorflowonspark_tpu.data.decode_plane import (
    DecodeAutotuner,
    DecodePlane,
    DecodeWorkerError,
)

pytestmark = pytest.mark.skipif(
    not decode_plane.available(), reason="no fork/shared_memory on this platform"
)


def _parse(rec):
    # module-level: fork-inheritable, deterministic per record bytes
    v = int(rec)
    if v < 0:
        raise ValueError("negative record {}".format(v))
    return np.full((4, 4, 1), v % 251, np.uint8), v


def _slow_parse(rec):
    time.sleep(0.05)
    return _parse(rec)


def _counter(name):
    return obs.snapshot()["counters"].get(name, {}).get("value", 0)


def _slab_files():
    return glob.glob("/dev/shm/tosslab_*")


@pytest.fixture
def plane():
    p = DecodePlane(_parse, workers=2)
    yield p
    p.close()


def _fill(plane, batch_size=8, records=None):
    images, labels = plane.new_slab(batch_size, (4, 4, 1), np.uint8)
    if records is None:
        records = [str(i).encode() for i in range(batch_size)]
    tasks = list(enumerate(records))
    failures = plane.run_round(images, labels, tasks)
    return images, labels, failures


class TestSlabSegment:
    def test_create_attach_roundtrip(self):
        slab = shm.SlabSegment.create(64)
        try:
            view = slab.ndarray((64,), np.uint8)
            view[:] = np.arange(64, dtype=np.uint8)
            other = shm.SlabSegment.attach(slab.name)
            got = np.array(other.ndarray((64,), np.uint8))
            other.close()
            assert (got == np.arange(64, dtype=np.uint8)).all()
        finally:
            slab.close()
            slab.unlink()
        assert slab.name not in [os.path.basename(f) for f in _slab_files()]

    def test_release_keeps_views_valid(self):
        # SharedMemory.close() unmaps under live views (segfault, not an
        # error) — release() hands the mapping to the views instead
        slab = shm.SlabSegment.create(16)
        view = slab.ndarray((16,), np.uint8)
        view[:] = 7
        name = slab.name
        slab.release()
        assert (view == 7).all()
        view[:] = 9  # still writable: the mapping follows the view
        assert "/dev/shm/" + name not in _slab_files()

    def test_unlink_leaked_covers_slabs(self, tmp_path):
        slab = shm.SlabSegment.create(16)
        name = slab.name
        slab.close()
        try:
            removed = shm.unlink_leaked(max_age_secs=0)
            assert removed >= 1
            assert "/dev/shm/" + name not in _slab_files()
        finally:
            # balance the create-side tracker registration for the segment
            # unlink_leaked removed behind the tracker's back
            shm._unregister_from_tracker(name)


class TestResolveWorkers:
    def test_explicit_count(self):
        assert decode_plane.resolve_workers(3) == (3, False)
        assert decode_plane.resolve_workers(0) == (0, False)
        assert decode_plane.resolve_workers(-2) == (0, False)

    def test_env_default(self, monkeypatch):
        monkeypatch.delenv("TOS_DECODE_WORKERS", raising=False)
        assert decode_plane.resolve_workers(None) == (0, False)
        monkeypatch.setenv("TOS_DECODE_WORKERS", "5")
        assert decode_plane.resolve_workers(None) == (5, False)

    def test_auto_self_sizes(self, monkeypatch):
        workers, auto = decode_plane.resolve_workers("auto")
        assert auto is True
        assert workers == max(1, (os.cpu_count() or 1) // 2)
        monkeypatch.setenv("TOS_DECODE_WORKERS", "auto")
        assert decode_plane.resolve_workers(None)[1] is True


class TestLeaseProtocol:
    def test_round_fills_slots_and_labels(self, plane):
        images, labels, failures = _fill(plane)
        assert failures == []
        for i in range(8):
            assert labels[i] == i
            assert (images[i] == i % 251).all()

    def test_worker_failures_come_back_as_errors(self, plane):
        records = [str(i if i != 3 else -7).encode() for i in range(8)]
        images, labels, failures = _fill(plane, records=records)
        assert len(failures) == 1
        slot, err = failures[0]
        assert slot == 3
        assert isinstance(err, DecodeWorkerError)
        assert "negative record -7" in str(err)
        # the other slots all landed
        for i in range(8):
            if i != 3:
                assert labels[i] == i

    def test_partial_round_leases_only_given_slots(self, plane):
        images, labels = plane.new_slab(8, (4, 4, 1), np.uint8)
        tasks = [(5, b"50"), (2, b"20")]
        assert plane.run_round(images, labels, tasks) == []
        assert labels[5] == 50 and labels[2] == 20

    def test_kill_mid_round_respawns_and_loses_no_slots(self):
        # SIGKILL one worker while it sleeps inside parse: the EOF on its
        # pipe must re-lease exactly its un-acked slots — every slot filled
        # exactly once, pool back at strength, restart counted
        plane = DecodePlane(_slow_parse, workers=2)
        try:
            before = _counter("decode_worker_restarts_total")
            images, labels = plane.new_slab(8, (4, 4, 1), np.uint8)
            victim = plane._workers[0].proc
            killer_done = []

            import threading

            def _kill():
                time.sleep(0.02)  # mid-round: workers are inside parse
                os.kill(victim.pid, signal.SIGKILL)
                killer_done.append(True)

            t = threading.Thread(target=_kill)
            t.start()
            failures = plane.run_round(
                images, labels, list(enumerate(str(i).encode() for i in range(8)))
            )
            t.join()
            assert killer_done and failures == []
            assert list(labels) == list(range(8))
            assert plane.workers == 2
            assert _counter("decode_worker_restarts_total") >= before + 1
        finally:
            plane.close()

    def test_stop_callback_raises_stopped(self, plane):
        images, labels = plane.new_slab(4, (4, 4, 1), np.uint8)
        with pytest.raises(decode_plane.Stopped):
            plane.run_round(images, labels, [(0, b"1")], should_stop=lambda: True)


class TestLifecycle:
    def test_resize_grows_and_shrinks(self, plane):
        plane.resize(4)
        assert plane.workers == 4
        plane.resize(1)
        assert plane.workers == 1
        # the shrunk pool still decodes
        images, labels, failures = _fill(plane, batch_size=4)
        assert failures == [] and list(labels) == [0, 1, 2, 3]

    def test_close_unlinks_slabs_and_reaps_workers(self):
        plane = DecodePlane(_parse, workers=2)
        images, labels, _ = _fill(plane)
        names = set(plane._slabs)
        procs = [w.proc for w in plane._workers]
        plane.close()
        plane.close()  # idempotent
        assert plane.workers == 0
        assert all(not p.is_alive() for p in procs)
        assert not any(
            os.path.basename(f) in names for f in _slab_files()
        )
        gauges = obs.snapshot()["gauges"]
        assert gauges["decode_workers"]["value"] == 0
        assert gauges["decode_slab_bytes"]["value"] == 0

    def test_slab_bytes_gauge_tracks_pool(self, plane):
        plane.new_slab(8, (4, 4, 1), np.uint8)
        plane.new_slab(8, (4, 4, 1), np.uint8)
        assert obs.snapshot()["gauges"]["decode_slab_bytes"]["value"] == 2 * 8 * 16

    def test_note_slab_wait_accumulates(self, plane):
        before = _counter("decode_slab_wait_seconds_total")
        plane.note_slab_wait(0.25)
        assert _counter("decode_slab_wait_seconds_total") == pytest.approx(
            before + 0.25
        )


class TestDecodeAutotuner:
    def test_starved_and_parse_dominated_grows_immediately(self):
        tuner = DecodeAutotuner(max_workers=8)
        assert tuner.decide(2, parse_delta=1.5, wait_delta=1.0, elapsed=2.0) == 3

    def test_starved_but_not_parse_dominated_holds(self):
        # the consumer starves yet parse is cheap: more decode workers
        # cannot help (IO or emit is the gate)
        tuner = DecodeAutotuner(max_workers=8)
        assert tuner.decide(2, parse_delta=0.1, wait_delta=1.0, elapsed=2.0) == 2

    def test_growth_respects_max_workers(self):
        tuner = DecodeAutotuner(max_workers=2)
        assert tuner.decide(2, parse_delta=2.0, wait_delta=1.0, elapsed=2.0) == 2

    def test_idle_shrinks_only_after_patience(self):
        tuner = DecodeAutotuner(max_workers=8, down_patience=2)
        assert tuner.decide(4, parse_delta=0.0, wait_delta=0.0, elapsed=2.0) == 4
        assert tuner.decide(4, parse_delta=0.0, wait_delta=0.0, elapsed=2.0) == 3

    def test_busy_interval_resets_the_down_streak(self):
        tuner = DecodeAutotuner(max_workers=8, down_patience=2)
        assert tuner.decide(4, parse_delta=0.0, wait_delta=0.0, elapsed=2.0) == 4
        # a mid-band interval (neither starved nor idle) clears the streak
        assert tuner.decide(4, parse_delta=0.1, wait_delta=0.06, elapsed=2.0) == 4
        assert tuner.decide(4, parse_delta=0.0, wait_delta=0.0, elapsed=2.0) == 4

    def test_shrink_respects_min_workers(self):
        tuner = DecodeAutotuner(min_workers=2, max_workers=8, down_patience=1)
        assert tuner.decide(2, parse_delta=0.0, wait_delta=0.0, elapsed=2.0) == 2

    def test_tick_is_clocked_and_delta_based(self):
        clock = [0.0]
        reads = [(0.0, 0.0), (3.0, 2.0), (3.0, 2.0)]
        tuner = DecodeAutotuner(
            max_workers=8,
            check_every=2.0,
            clock=lambda: clock[0],
            read_counters=lambda: reads.pop(0),
        )
        assert tuner.tick(2) is None  # first call seeds the baseline
        clock[0] = 1.0
        assert tuner.tick(2) is None  # interval not elapsed: no read burned
        clock[0] = 2.5
        # deltas (3.0, 2.0) over 2.5 s: starved and parse-dominated → grow
        assert tuner.tick(2) == 3
        clock[0] = 5.0
        # zero deltas: idle, but down_patience=2 holds the first time
        assert tuner.tick(3) == 3


def _pid_noting_parse(log, rec):
    fd = os.open(log, os.O_WRONLY | os.O_APPEND | os.O_CREAT)
    try:
        os.write(fd, b"%d\n" % os.getpid())
    finally:
        os.close(fd)
    return _parse(rec)


@pytest.mark.perf_smoke
class TestGilRelease:
    """The point of the plane, as far as a test can show it without a clock:
    with ``decode_workers=4`` the parse_fn runs in processes of its own,
    off the parent's GIL (all but the first record, which the parent
    parses to size the slabs), and the stream they fill is the thread
    pool's, byte for byte. How much faster that is depends on the cores the
    host has free (under the suite's own workers: none), so the speed is
    ROADMAP S3's to read on the chip's host beside the JPEG cell, not a
    ratio asserted here."""

    @pytest.mark.parametrize(
        "records,batches,min_pids",
        [
            (96, 9, 2),
            pytest.param(
                160, 13, 4,
                marks=pytest.mark.skipif(
                    (os.cpu_count() or 1) < 4,
                    reason="needs >= 4 cores for four workers to be worth having",
                ),
            ),
        ],
    )
    def test_process_pool_parses_off_the_parents_gil(
        self, tmp_path, records, batches, min_pids
    ):
        from tensorflowonspark_tpu import tfrecord
        from tensorflowonspark_tpu.data import ImagePipeline

        p = str(tmp_path / "part-00000")
        with tfrecord.TFRecordWriter(p) as w:
            for i in range(records):
                w.write(str(i).encode())

        def _stream(decode_workers):
            log = str(tmp_path / "pids-{}".format(decode_workers))
            pipe = ImagePipeline(
                [p], functools.partial(_pid_noting_parse, log), batch_size=8,
                seed=0, epochs=None,
                num_threads=1, decode_workers=decode_workers,
            )
            it = iter(pipe)
            out = []
            for _ in range(batches):
                b = next(it)
                out.append((np.array(b["image"]), np.array(b["label"])))
            del it
            with open(log) as f:
                return out, collections.Counter(int(line) for line in f)

        thread, thread_pids = _stream(0)
        procs, proc_pids = _stream(4)
        assert set(thread_pids) == {os.getpid()}
        # the parent parses one record, the first, whose shape sizes the slabs
        assert proc_pids.pop(os.getpid()) == 1
        assert len(proc_pids) >= min_pids, proc_pids
        assert sum(proc_pids.values()) >= batches * 8 - 1
        for (ti, tl), (pi, pl) in zip(thread, procs):
            assert ti.tobytes() == pi.tobytes()
            assert tl.tobytes() == pl.tobytes()
