"""Unit tests for bench.py's result-annotation helpers (the heavy benchmark
paths themselves run under BENCH_* env switches, not pytest)."""

import importlib.util
import os

import pytest

_BENCH = os.path.join(os.path.dirname(__file__), "..", "bench.py")
_spec = importlib.util.spec_from_file_location("bench", _BENCH)
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def test_confidence_fields_full_budget():
    # all requested pairs recorded and valid: no low-confidence flag
    assert bench.confidence_fields(6, 6) == {
        "pairs": 6, "pairs_requested": 6, "pairs_completed": 6,
    }
    assert bench.confidence_fields(7, 6) == {
        "pairs": 7, "pairs_requested": 6, "pairs_completed": 7,
    }


def test_confidence_fields_short_run_flags_low_confidence():
    out = bench.confidence_fields(3, 6)
    assert out == {
        "pairs": 3, "pairs_requested": 6, "pairs_completed": 3,
        "low_confidence": True,
    }


def test_confidence_fields_budget_exhausted_is_reported():
    # the budget (not the rep count) ended the run: say so explicitly, on
    # top of the sample-count accounting
    out = bench.confidence_fields(3, 6, budget_exhausted=True)
    assert out == {
        "pairs": 3, "pairs_requested": 6, "pairs_completed": 3,
        "budget_exhausted": True, "low_confidence": True,
    }
    # a full run never carries the flag
    assert "budget_exhausted" not in bench.confidence_fields(6, 6)


def test_confidence_fields_zero_pairs():
    out = bench.confidence_fields(0, 6)
    assert out["pairs"] == 0 and out["low_confidence"] is True


def test_confidence_fields_invalid_pairs_lower_confidence():
    # 6 pairs ran but one was discarded: the median rests on 5 samples
    out = bench.confidence_fields(6, 6, invalid_pairs=1)
    assert out["pairs"] == 6
    assert out["invalid_pairs"] == 1
    assert out["pairs_completed"] == 5
    assert out["low_confidence"] is True


def test_partition_pairs_flags_impossible_ratios():
    # train cannot beat its own input path: the 3.30 pair is noise
    nc = [100.0, 100.0, 100.0]
    tr = [95.0, 330.0, 102.0]
    valid, invalid = bench.partition_pairs(nc, tr)
    assert valid == [(100.0, 95.0), (100.0, 102.0)]
    assert invalid == [(100.0, 330.0)]


def test_partition_pairs_boundary_is_inclusive():
    valid, invalid = bench.partition_pairs([100.0], [110.0])
    assert valid and not invalid  # ratio == 1.10 exactly: still valid
    valid, invalid = bench.partition_pairs([100.0], [111.0])
    assert invalid and not valid


def test_partition_pairs_all_valid():
    valid, invalid = bench.partition_pairs([100.0, 90.0], [99.0, 91.0])
    assert len(valid) == 2 and not invalid


def test_partition_pairs_band_is_symmetric():
    # a train block 12% SLOWER than its paired input-path block is just as
    # impossible under the pairing model as 12% faster (the r05 0.881 pair:
    # the link's rate swung between the two half-blocks) — both sides
    # of the band discard
    valid, invalid = bench.partition_pairs([100.0, 100.0], [88.1, 95.0])
    assert valid == [(100.0, 95.0)]
    assert invalid == [(100.0, 88.1)]


def test_partition_pairs_low_boundary_is_inclusive():
    # ratio == 1/1.10 exactly: still valid, mirroring the high boundary
    valid, invalid = bench.partition_pairs([110.0], [100.0])
    assert valid and not invalid
    valid, invalid = bench.partition_pairs([113.0], [100.0])
    assert invalid and not valid


def test_seed_autotuner_solves_the_two_probe_system():
    """fixed=(K*t_pb - t_win)/(K-1), bw from the residual stream time: a
    synthetic link with known parameters must round-trip through the probe
    rates exactly."""
    from tensorflowonspark_tpu.data import FeedAutotuner

    fixed, bw = 0.25, 20e6
    # the real bench batch: 64 uint8 images at 224x224x3 (~9.6 MB)
    batch_imgs, win = 64, 8
    batch_bytes = 64 * 224 * 224 * 3
    t_pb = fixed + batch_bytes / bw            # seconds per per-batch transfer
    t_win = fixed + win * batch_bytes / bw     # seconds per packed window
    per_batch_rate = batch_imgs / t_pb
    packed_rate = win * batch_imgs / t_win

    tuner = FeedAutotuner()
    assert bench.seed_autotuner(
        tuner, per_batch_rate, packed_rate, win, batch_imgs, batch_bytes
    )
    assert tuner.estimator.ready
    assert tuner.estimator.fixed_s == pytest.approx(fixed, rel=1e-6)
    assert tuner.estimator.bytes_per_sec == pytest.approx(bw, rel=1e-6)
    # at these parameters the controller recommends the hand-tuned K=8
    assert tuner.recommend(batch_bytes) == 8


def test_seed_autotuner_refuses_unusable_probes():
    from tensorflowonspark_tpu.data import FeedAutotuner

    tuner = FeedAutotuner()
    assert not bench.seed_autotuner(tuner, 0.0, 100.0, 8, 64, 1 << 20)
    assert not bench.seed_autotuner(tuner, 100.0, 100.0, 1, 64, 1 << 20)
    assert not tuner.estimator.ready


def test_feed_fields_reports_link_estimate_and_stalls():
    from tensorflowonspark_tpu.data import FeedAutotuner

    tuner = FeedAutotuner()
    out = bench.feed_fields(tuner, window_k=1, batch_bytes=1 << 20)
    assert out["window_k"] == 1
    assert "autotuned_k" not in out  # estimator unseeded: no link estimate
    assert set(out["stalls"]) == {
        "producer_read_seconds", "producer_parse_seconds",
        "producer_emit_seconds", "consumer_wait_seconds",
        "classification", "store",
    }
    assert out["stalls"]["classification"] in {
        "device_bound", "decode_bound", "io_bound",
    }
    # store provenance rides in the stalls block: backend fingerprint plus
    # the per-tier hit/miss/promotion counters
    store = out["stalls"]["store"]
    assert isinstance(store["backend"], str) and store["backend"]
    for k in ("remote_reads", "prefetch_hits", "tier_ram_hits",
              "tier_disk_hits", "tier_promotions"):
        assert isinstance(store[k], int)

    tuner.note_fixed_probe(0.25)
    tuner.note_transfer(1 << 20, 0.25 + (1 << 20) / 20e6)
    out = bench.feed_fields(tuner, window_k=8, batch_bytes=1 << 20)
    assert out["window_k"] == 8
    assert out["autotuned_k"] in tuner.buckets
    assert out["link_fixed_cost_seconds"] == pytest.approx(0.25, abs=1e-3)
    assert out["link_bytes_per_sec"] == pytest.approx(20e6, rel=1e-2)


def test_classify_stalls_covers_all_three_bottlenecks():
    # producer blocked on the full queue >= consumer starvation: device gates
    assert bench.classify_stalls(1.0, 1.0, 5.0, 2.0) == "device_bound"
    # input path gates, parse dominates shard IO: the decode stage
    assert bench.classify_stalls(1.0, 3.0, 0.0, 2.0) == "decode_bound"
    # input path gates, shard IO dominates parse
    assert bench.classify_stalls(3.0, 1.0, 0.0, 2.0) == "io_bound"


def test_least_implausible_pair_picks_log_symmetric_winner():
    # ratios 3.30, 0.5, 2.0 — |log| says 2.0 and 0.5 tie at log 2, 3.30
    # loses; min() resolves the tie to the first, but the outlier must
    # never win
    nc = [100.0, 100.0, 100.0]
    tr = [330.0, 50.0, 200.0]
    assert bench.least_implausible_pair(nc, tr) in {(100.0, 50.0), (100.0, 200.0)}

    # an actual near-1.0 ratio beats both halves of the band
    tr2 = [330.0, 50.0, 108.0]
    assert bench.least_implausible_pair(nc, tr2) == (100.0, 108.0)

    # symmetric: 0.9 and 1/0.9 are equally plausible, both beat 3.30
    assert bench.least_implausible_pair([100.0, 100.0], [90.0, 330.0]) == (100.0, 90.0)


def test_all_invalid_fallback_admits_one_pair_not_the_raw_set():
    # the r05 regression: every pair out of band used to readmit the whole
    # raw set, letting a 3.30 outlier into the headline median — the
    # fallback must now surface exactly one least-implausible pair
    nc = [100.0, 100.0]
    tr = [330.0, 250.0]
    valid, invalid = bench.partition_pairs(nc, tr)
    assert valid == []
    assert len(invalid) == 2
    best = bench.least_implausible_pair(nc, tr)
    assert best == (100.0, 250.0)
