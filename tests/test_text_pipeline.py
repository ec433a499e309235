"""Text plane: tokenizer validation, FFD packing bounds, the TextPipeline
determinism contract (byte-identical [B, L] streams across pack modes,
knobs, and cache states), the text chaos sites, the TFEstimator LM
fine-tune wiring, and the perf-smoke lm leg."""


import numpy as np
import pytest

from tensorflowonspark_tpu import chaos, obs, tfrecord
from tensorflowonspark_tpu.control import classify_stalls
from tensorflowonspark_tpu.data import TextPipeline, TokenizeError, Tokenizer, pack_bins
from tensorflowonspark_tpu.data.tokenizer import BOS_ID, EOS_ID, PAD_ID, RESERVED_IDS


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.uninstall()
    yield
    chaos.uninstall()


def _write_corpus(tmp_path, texts, shards=2, name="corpus"):
    d = tmp_path / name
    d.mkdir(exist_ok=True)
    per = (len(texts) + shards - 1) // shards
    paths = []
    for s in range(shards):
        p = str(d / "part-{:05d}".format(s))
        with tfrecord.TFRecordWriter(p) as w:
            for t in texts[s * per : (s + 1) * per]:
                w.write(t if isinstance(t, bytes) else t.encode("utf-8"))
        paths.append(p)
    return paths


def _sample_texts(n=120, seed=0):
    rng = np.random.default_rng(seed)
    words = "spark text plane packs variable length sequences tightly".split()
    return [
        " ".join(rng.choice(words, size=max(2, int(rng.lognormal(2.2, 0.7)))))
        for _ in range(n)
    ]


def _collect(pipe):
    return [{k: np.array(v) for k, v in b.items()} for b in pipe]


def _streams_equal(a, b):
    if len(a) != len(b):
        return False
    for x, y in zip(a, b):
        for k in ("tokens", "segment_ids", "positions"):
            if not np.array_equal(x[k], y[k]):
                return False
    return True


class TestTokenizer:
    def test_byte_roundtrip_shape(self):
        tok = Tokenizer(kind="byte")
        ids = tok.encode(b"hi")
        assert list(ids) == [BOS_ID, ord("h") + RESERVED_IDS, ord("i") + RESERVED_IDS, EOS_ID]
        assert tok.token_length(b"hi") == len(ids)

    def test_word_hashing_is_deterministic(self):
        tok = Tokenizer(kind="word", vocab_size=64)
        a, b = tok.encode(b"alpha beta alpha"), tok.encode(b"alpha beta alpha")
        assert np.array_equal(a, b)
        assert a[1] == a[3]  # same word, same bucket
        assert all(RESERVED_IDS <= t < 64 for t in a[1:-1])

    def test_truncation_keeps_terminal_eos(self):
        tok = Tokenizer(kind="byte")
        ids = tok.encode(b"abcdefgh", max_tokens=5)
        assert len(ids) == 5 and ids[0] == BOS_ID and ids[-1] == EOS_ID

    def test_rejects_invalid_utf8_and_empty(self):
        tok = Tokenizer()
        with pytest.raises(TokenizeError):
            tok.token_length(b"\xff\xfe")
        with pytest.raises(TokenizeError):
            tok.token_length(b"   ")

    def test_example_field_extraction(self):
        tok = Tokenizer(kind="word", field="text")
        rec = tfrecord.encode_example({"text": [b"hello world"]})
        assert tok.token_length(rec) == 4
        with pytest.raises(TokenizeError):
            tok.token_length(tfrecord.encode_example({"other": [b"x"]}))

    def test_cache_key_covers_config(self):
        keys = {
            Tokenizer().cache_key,
            Tokenizer(kind="word").cache_key,
            Tokenizer(kind="word", vocab_size=64).cache_key,
            Tokenizer(kind="word", field="text").cache_key,
        }
        assert len(keys) == 4


class TestPackBins:
    def test_partition_is_exact_and_within_capacity(self):
        rng = np.random.default_rng(0)
        lengths = rng.integers(1, 101, 500).tolist()
        bins = pack_bins(lengths, 100)
        flat = sorted(i for b in bins for i in b)
        assert flat == list(range(len(lengths)))
        assert all(sum(lengths[i] for i in b) <= 100 for b in bins)

    @pytest.mark.parametrize(
        "name,lengths",
        [
            # classic FFD adversary: halves + quarters + slack
            ("halves", [51] * 20 + [26] * 20 + [23] * 20),
            # heavy head, long tail of crumbs
            ("zipf", [90] * 5 + [40] * 10 + [7] * 200),
            # all just over a third: exactly 2 per bin, 1/3 wasted
            ("thirds", [34] * 30),
            ("uniform", list(np.random.default_rng(1).integers(1, 101, 400))),
        ],
    )
    def test_ffd_bound_on_adversarial_distributions(self, name, lengths):
        # FFD <= 11/9 OPT + 6/9 (Dósa); OPT >= ceil(total/capacity)
        capacity = 100
        bins = pack_bins(lengths, capacity)
        lb = -(-sum(lengths) // capacity)
        assert len(bins) <= (11 * lb + 6) // 9 + 1, name

    def test_determinism_and_creation_order(self):
        lengths = [10, 3, 7, 5, 2]
        assert pack_bins(lengths, 12) == pack_bins(lengths, 12) == [[0, 4], [2, 3], [1]]


class TestDeterminism:
    """The delivered [B, L] stream is byte-identical across pack worker
    counts, pipeline knobs, and packed-slab cache states."""

    def _pipe(self, files, tmp_path, **kw):
        kw.setdefault("seq_len", 48)
        kw.setdefault("batch_size", 4)
        kw.setdefault("seed", 7)
        kw.setdefault("epochs", 2)
        return TextPipeline(files, Tokenizer(kind="word", vocab_size=128), **kw)

    def test_stream_invariant_across_pack_modes_and_knobs(self, tmp_path):
        files = _write_corpus(tmp_path, _sample_texts())
        base = _collect(self._pipe(files, tmp_path))
        assert base, "pipeline yielded nothing"
        assert _streams_equal(base, _collect(self._pipe(files, tmp_path, pack_workers=2)))
        assert _streams_equal(
            base,
            _collect(
                self._pipe(
                    files, tmp_path, readahead=0, chunk_records=8, num_threads=1
                )
            ),
        )

    def test_stream_invariant_across_cache_states(self, tmp_path):
        files = _write_corpus(tmp_path, _sample_texts(seed=3))
        cache_dir = str(tmp_path / "slabs")
        base = _collect(self._pipe(files, tmp_path))
        cold = _collect(self._pipe(files, tmp_path, slab_cache_dir=cache_dir))
        warm = _collect(self._pipe(files, tmp_path, slab_cache_dir=cache_dir))
        assert _streams_equal(base, cold)
        assert _streams_equal(base, warm)

    def test_batches_are_packed_and_position_fenced(self, tmp_path):
        files = _write_corpus(tmp_path, _sample_texts(seed=5))
        for batch in _collect(self._pipe(files, tmp_path)):
            tokens, seg, pos = batch["tokens"], batch["segment_ids"], batch["positions"]
            assert tokens.shape == seg.shape == pos.shape == (4, 48)
            # pad iff segment 0; positions restart at 0 per segment
            assert np.array_equal(seg == 0, tokens == PAD_ID) or (tokens[seg == 0] == PAD_ID).all()
            for row_seg, row_pos in zip(seg, pos):
                for s in np.unique(row_seg[row_seg > 0]):
                    span = row_pos[row_seg == s]
                    assert list(span) == list(range(len(span)))


    @pytest.mark.parametrize("pack_workers", [0, 2])
    def test_flash_block_counters_follow_the_emitted_rows(self, tmp_path, pack_workers):
        """flash_blocks_needed_total / _dense_total / flash_grid_steps_total
        grow by what the segmented kernels compute, would have computed
        densely, and walk (the batch's longest list a row), for the columns
        the LM attends of every emitted batch."""
        from tensorflowonspark_tpu.ops import flash_blocks

        def counts():
            found = obs.snapshot()["counters"]
            return [found.get(name, {"value": 0})["value"]
                    for name in ("flash_blocks_needed_total", "flash_blocks_dense_total", "flash_grid_steps_total")]

        # documents of 100-400 tokens in rows of 4096+1, the benchmark's rows
        rng = np.random.default_rng(1)
        texts = [" ".join(["w{}".format(i % 50)] * int(rng.integers(100, 400))) for i in range(200)]
        files = _write_corpus(tmp_path, texts)
        before = counts()
        batches = _collect(self._pipe(files, tmp_path, seq_len=4097, epochs=1, pack_workers=pack_workers))
        assert batches
        want = np.sum([flash_blocks.attended_blocks(b["segment_ids"][:, :-1]) for b in batches], axis=0)
        got = np.subtract(counts(), before)
        assert list(got) == list(want)
        assert 0 < got[0] <= got[2] < got[1]


class TestBadRecords:
    def test_budget_charged_identically_in_every_mode(self, tmp_path):
        texts = _sample_texts(40)
        texts[5] = b"\xff\xfe broken"
        texts[21] = b"\x80\x80 also broken"
        files = _write_corpus(tmp_path, texts)

        def run(**kw):
            before = obs.counter("text_tokenize_errors_total").value
            pipe = TextPipeline(
                files, Tokenizer(), seq_len=64, batch_size=2, seed=1,
                max_bad_records=2, **kw
            )
            batches = _collect(pipe)
            return batches, obs.counter("text_tokenize_errors_total").value - before

        b0, skipped0 = run()
        b2, skipped2 = run(pack_workers=2)
        assert skipped0 == skipped2 == 2
        assert _streams_equal(b0, b2)

    def test_budget_exhaustion_raises(self, tmp_path):
        texts = _sample_texts(20)
        texts[3] = b"\xff\xfe broken"
        files = _write_corpus(tmp_path, texts)
        pipe = TextPipeline(
            files, Tokenizer(), seq_len=64, batch_size=2, seed=1, max_bad_records=0
        )
        with pytest.raises(TokenizeError):
            _collect(pipe)


class TestChaosSites:
    def test_tokenize_error_charged_to_budget_mode_invariant(self, tmp_path):
        files = _write_corpus(tmp_path, _sample_texts(60, seed=9))

        def run(**kw):
            chaos.uninstall()
            chaos.install(
                chaos.ChaosPlan(seed=11).site(
                    "data.tokenize_error", probability=1.0, max_count=3
                )
            )
            before = obs.counter("text_tokenize_errors_total").value
            pipe = TextPipeline(
                files, Tokenizer(), seq_len=64, batch_size=2, seed=1,
                max_bad_records=3, **kw
            )
            batches = _collect(pipe)
            return batches, obs.counter("text_tokenize_errors_total").value - before

        b0, s0 = run()
        b2, s2 = run(pack_workers=2)
        assert s0 == s2 == 3
        assert _streams_equal(b0, b2)
        assert obs.counter("chaos_fault_data_tokenize_error_total").value >= 6

    def test_pack_stall_is_charged_input_bound(self, tmp_path):
        files = _write_corpus(tmp_path, _sample_texts(80, seed=4))
        chaos.install(
            chaos.ChaosPlan(seed=2).site(
                "data.pack_stall", probability=1.0, max_count=None, delay_s=0.02
            )
        )
        snap0 = obs.snapshot()["counters"]

        def _d(name):
            return (
                obs.snapshot()["counters"].get(name, {}).get("value", 0.0)
                - snap0.get(name, {}).get("value", 0.0)
            )

        pipe = TextPipeline(
            files, Tokenizer(), seq_len=48, batch_size=2, seed=1, readahead=0
        )
        assert _collect(pipe)
        stall = _d("text_pack_stall_seconds_total")
        assert stall > 0, "pack_stall delay was not charged"
        # the injected delay lands in parse time: the classifier must call
        # the run input-bound (decode_bound), not io/device bound
        assert (
            classify_stalls(
                _d("data_producer_read_seconds_total"),
                _d("data_producer_parse_seconds_total"),
                0.0,  # producer never blocked on the queue in this drain
                _d("data_consumer_wait_seconds_total") + stall,
            )
            == "decode_bound"
        )
        assert _d("chaos_fault_data_pack_stall_total") > 0


class TestEstimatorLMFinetune:
    """The pipeline-API wiring: a DataFrame of text rows materialized via
    setTFRecordDir, a train_fn that fine-tunes a tiny LM by reading those
    shards through TextPipeline with a field-extracting Tokenizer, and the
    text_* metrics surfacing in the estimator's captured cluster metrics."""

    def test_finetune_through_tfrecord_dir(self, tmp_path):
        from tensorflowonspark_tpu import dfutil, pipeline
        from tensorflowonspark_tpu.backends.local import LocalSparkContext

        tfr_dir = str(tmp_path / "tfr")
        sc = LocalSparkContext(num_executors=2, task_timeout=300)
        try:
            texts = _sample_texts(64, seed=13)
            df = sc.createDataFrame([(t,) for t in texts], ["text"], 2)
            est = (
                pipeline.TFEstimator(
                    _lm_finetune_fn, {"steps": 4}, env={"JAX_PLATFORMS": "cpu"}
                )
                .setInputMapping({"text": "text"})
                .setEpochs(1)
                .setClusterSize(2)
                .setMasterNode(None)
                .setTFRecordDir(tfr_dir)
            )
            est.fit(df)
            assert dfutil.tfrecord.list_shards(tfr_dir), "shards not materialized"
            counters = est.cluster_metrics_["counters"]
            assert counters["text_sequences_packed_total"]["value"] > 0
            assert counters["text_tokens_packed_total"]["value"] > 0
            # the cluster-level gauge is a SUM across sources (aggregate.py
            # semantic) and include_driver=True folds in the driver's own
            # registry — which mid-suite carries whatever earlier in-process
            # tests left there. The per-node views are spawn-clean: each
            # executor's efficiency must be a real ratio in (0, 1].
            effs = [
                node["gauges"]["text_pack_efficiency"]["value"]
                for node in est.cluster_metrics_["nodes"].values()
                if "text_pack_efficiency" in node["gauges"]
            ]
            assert effs and all(0.0 < e <= 1.0 for e in effs), effs
        finally:
            sc.stop()


def _lm_finetune_fn(args, ctx):
    # module-level: must be picklable into the executor processes
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu import parallel, tfrecord
    from tensorflowonspark_tpu.data import TextPipeline, Tokenizer, shard_files
    from tensorflowonspark_tpu.models import transformer
    from tensorflowonspark_tpu.train import SyncDataParallel

    # drain the spark feed (InputMode.SPARK contract) while the real input
    # comes from the materialized TFRecord shards
    feed = ctx.get_data_feed(train_mode=True)

    batch = jax.device_count()  # dp=-1 mesh below: batch divides the mesh
    files = shard_files(
        tfrecord.list_shards(args.tfrecord_dir), ctx.num_workers, ctx.executor_id
    )
    pipe = TextPipeline(
        files, Tokenizer(kind="word", vocab_size=128, field="text"),
        seq_len=33, batch_size=batch, seed=ctx.executor_id, epochs=None,
        drop_remainder=True,
    )
    stream = iter(pipe)

    mesh = parallel.local_mesh({"dp": -1})
    model = transformer.create_model(
        mesh=mesh, vocab_size=128, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        dtype="float32",
    )
    strategy = SyncDataParallel(mesh)
    optimizer = optax.adamw(1e-3)
    state = strategy.create_state(
        transformer.make_init_fn(model, sample_len=8), optimizer,
        jax.random.PRNGKey(0),
    )
    step = strategy.compile_train_step(
        transformer.make_loss_fn(model), optimizer, has_aux=True
    )
    losses = []
    for _ in range(int(args.steps)):
        state, metrics = step(state, strategy.shard_batch(next(stream)))
        losses.append(float(np.asarray(jax.device_get(metrics["loss"]))))
    stream.close()
    assert all(np.isfinite(losses)), losses
    while not feed.should_stop():
        feed.next_batch(16)
