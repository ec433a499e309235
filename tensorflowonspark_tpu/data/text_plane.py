"""Sequence-packed tokenized-text input pipeline (the text plane).

:class:`TextPipeline` is :class:`~tensorflowonspark_tpu.data.loader.
ImagePipeline`'s contract transplanted onto variable-length text: stages
1+2 (shard read-ahead over the chunked-read ABI, bounded shuffle, raw
cache, ``max_bad_records``, the ``data.shard_read`` /
``data.readahead_stall`` chaos seams) are inherited verbatim, and stage 3
replaces fixed-geometry batch assembly with **sequence packing**: records
are tokenized and first-fit-decreasing bin-packed into fixed ``[B, L]``
int32 buffers (T5-style packing, Raffel et al. 2020), so the accelerator
sees one static shape regardless of the length distribution.

Each emitted batch is ``{"tokens", "segment_ids", "positions"}``, all
``int32 [B, L]`` views of one ``[B, 3, L]`` buffer:

- ``tokens`` — packed ids, 0 (PAD) in the slack;
- ``segment_ids`` — 0 for padding, 1..n per packed sequence, the
  cross-attention fence :mod:`~tensorflowonspark_tpu.models.transformer`
  turns into a block-diagonal attention mask (flash and ring included);
- ``positions`` — restart at 0 per segment so rotary phases never leak
  across pack neighbours.

Packing runs producer-side as a *plan* (lengths only, via the tokenizer's
cheap validating :meth:`~tensorflowonspark_tpu.data.tokenizer.Tokenizer.
token_length`), then the plan's cache misses are tokenized either on the
in-process thread pool or — with ``pack_workers > 0`` — in the decode
plane's forked workers writing straight into shared-memory slabs under the
slot-lease protocol (:mod:`~tensorflowonspark_tpu.data.decode_plane`; the
payload is the pack plan, one lease per packed row). Because the plan, the
budget accounting, and the zeroing all happen in the producer thread, the
delivered ``[B, L]`` stream is **byte-identical** across ``pack_workers``
settings, readahead/chunk knobs, and packed-slab cache states (cold, warm,
off) — the same determinism contract the image plane enforces.

The packed-slab cache (:mod:`~tensorflowonspark_tpu.data.slab_cache`) is
reused with per-*sequence* geometry ``(L,) int32``: rows are keyed by
record crc32 under the tokenizer-config fingerprint (kind, vocab, field,
``L`` — truncation depends on the bin capacity), the row label is the
effective token count, and epoch >= 2 (or a warm relaunch) serves token
ids from a memory map instead of re-tokenizing.

**Noising for diffusion over blocks** (``block_diffusion={"block_length",
"mask_id", "t_min"}``). A further producer stage, after the pack: a
document's positions fall in blocks of ``block_length``, counted from its
start; every block of a real document draws a rate ``t ~ U(t_min, 1)`` and
each of its tokens becomes ``mask_id`` with probability ``t``. The batch then
also carries ``noised_tokens`` (``int32 [B, L]``) and ``loss_weights``
(``float32 [B, L]``: ``1 / t`` at a masked position, else 0), which
``models.transformer.make_block_diffusion_loss_fn`` consumes. The draws come
from the pipeline's ``seed`` and the batch's index in the stream, in the
producer thread, so the noise is as reproducible as the packing. Documents
then start on multiples of ``block_length`` in their row (up to
``block_length - 1`` slots of padding each), so that no block of the
diffusion straddles a block of the attention kernels, and the ``flash_*``
counters follow the rows as that model reads them (both copies, the
block-diffusion rule). Timed by the span ``producer_noise``
(``data_producer_noise_seconds_total``) and counted in
``bd_positions_masked_total`` / ``bd_tokens_real_total``.

**A model with windowed layers** (``attention_window=``: the window its
windowed layers attend within) reads the same rows under two rules, and its
windowed layers need other blocks of them than its full ones. The producer
then also feeds the ``flash_win_*`` counters, by the numpy twin of the
kernels' third rule: blocks needed, the triangle's, the grid steps, and the
pairs the rule shows over the pairs of the blocks computed.

**A model with state-space layers** (``scan_restarts=True``) scans every row
position by position and starts anew wherever a document starts. The producer
then counts what those scans walk and restart on, from the rows' ids alone:
``ssm_scan_positions_total`` (the positions the model reads, a row's
``seq_len - 1``) and ``ssm_scan_restarts_total`` (the positions whose id
differs from the one before, and every row's first: the rule of
:func:`~tensorflowonspark_tpu.ops.selective_scan.restarts`).

Chaos sites native to this stage: ``data.tokenize_error`` poisons a
record's bytes producer-side so the tokenizer rejects it (charged against
``max_bad_records``, identically in every pack mode) and
``data.pack_stall`` injects a delay inside the timed pack region, charged
to parse time so the stall classifier reports the run input-bound.
"""

import logging
import queue
import threading
import time
import zlib

import numpy as np

from tensorflowonspark_tpu import chaos, obs
from tensorflowonspark_tpu.data import decode_plane, slab_cache
from tensorflowonspark_tpu.data import tokenizer as tokenizer_mod
from tensorflowonspark_tpu.data.loader import ImagePipeline, _Stopped
from tensorflowonspark_tpu.ops import flash_blocks

logger = logging.getLogger(__name__)

__all__ = ["TextPipeline", "pack_bins", "noise_blocks"]

#: invalid UTF-8 the ``data.tokenize_error`` site swaps in for a record
_CHAOS_BAD_RECORD = b"\xff\xfe chaos-malformed-text-record"


def pack_bins(lengths, capacity):
    """First-fit-decreasing bin packing of ``lengths`` into bins of
    ``capacity``. Returns bins in creation order, each a list of indices
    into ``lengths`` in placement (descending-length, arrival-stable)
    order. Pure and deterministic — the packing *plan* is computed once,
    producer-side, and every pack mode executes the same plan.

    FFD's classic guarantee (11/9 OPT + 6/9, Dósa 2007) is what bounds the
    pad waste the efficiency tests assert on adversarial distributions.
    """
    order = sorted(range(len(lengths)), key=lambda i: (-lengths[i], i))
    bins = []  # [used, [idx, ...]]
    for i in order:
        n = lengths[i]
        for b in bins:
            if b[0] + n <= capacity:
                b[0] += n
                b[1].append(i)
                break
        else:
            bins.append([n, [i]])
    return [b[1] for b in bins]


def noise_blocks(tokens, segment_ids, positions, block_length, mask_id, t_min, rng):
    """Block-diffusion noise for packed rows (``int32 [B, L]`` each):
    ``(noised_tokens int32 [B, L], loss_weights float32 [B, L])``. One rate
    ``t ~ U(t_min, 1)`` a diffusion block (``block_length`` positions of one
    document, counted from its start), every real token masked with its
    block's probability; a masked position weighs ``1 / t``. The draws are
    two arrays of the rows' shape whatever the packing, so a seeded ``rng``
    gives the same noise for the same rows."""
    rate_at = rng.uniform(t_min, 1.0, size=tokens.shape)
    coin = rng.random(size=tokens.shape)
    # a block's rate is the one drawn at its first position
    rate = np.take_along_axis(rate_at, np.arange(tokens.shape[1])[None, :] - positions % block_length, axis=1)
    masked = (coin < rate) & (segment_ids > 0)
    return (np.where(masked, np.int32(mask_id), tokens).astype(np.int32),
            np.where(masked, 1.0 / rate, 0.0).astype(np.float32))


class TextPipeline(ImagePipeline):
    """files -> shuffled, tokenized, sequence-packed batches of
    ``{"tokens", "segment_ids", "positions"}`` (all ``int32 [B, L]``).

    Mirrors :class:`~tensorflowonspark_tpu.data.loader.ImagePipeline`'s
    constructor and determinism contract; the differences:

    - ``tokenizer`` + ``seq_len`` replace ``parse_fn`` (the pack-plane
      parse fn is built internally via :func:`~tensorflowonspark_tpu.data.
      tokenizer.make_pack_fn`);
    - ``pack_workers`` is the text plane's ``decode_workers`` (0 = thread
      pool, ``"auto"``/N = forked slab workers);
    - ``pack_ahead`` sizes the packing window: records accumulate until
      roughly ``pack_ahead * B * L`` tokens are pending, then the window
      is FFD-packed — deeper windows pack tighter, at more producer
      buffering (leftover part-full bins carry their sequences into the
      next window, so nothing is dropped mid-stream);
    - ``block_diffusion`` (a dict: ``block_length``, ``mask_id``, ``t_min``
      default 1e-3) adds the noising stage and its batch keys (the module's
      text);
    - ``attention_window`` (the window of the model's windowed layers) adds
      the ``flash_win_*`` counters beside the ``flash_*`` ones;
    - ``scan_restarts`` (the model has state-space layers) adds
      ``ssm_scan_positions_total`` and ``ssm_scan_restarts_total``;
    - ``cache="decoded"`` and ``recycle_buffers`` are not supported (the
      decoded-pair cache is image-geometry machinery; packed rows already
      have the packed-slab cache).

    ``max_bad_records`` budgets records the tokenizer rejects (malformed
    UTF-8, empty text, missing Example feature) exactly like undecodable
    images: skipped and counted until the budget is spent, then the
    :class:`~tensorflowonspark_tpu.data.tokenizer.TokenizeError` surfaces
    to the consumer. Sequences longer than ``L`` are not errors — they are
    truncated (terminal EOS kept) and counted in
    ``text_sequences_truncated_total``.
    """

    def __init__(
        self,
        files,
        tokenizer,
        seq_len,
        batch_size,
        shuffle=True,
        seed=0,
        num_threads=None,
        epochs=1,
        prefetch_batches=2,
        verify_crc=False,
        drop_remainder=True,
        max_bad_records=0,
        readahead=None,
        chunk_records=None,
        shuffle_buffer=4096,
        cache=None,
        pack_workers=None,
        pack_ahead=2.0,
        slab_cache_dir=None,
        store=None,
        prefetch=None,
        block_diffusion=None,
        attention_window=None,
        scan_restarts=False,
    ):
        if cache == "decoded":
            raise ValueError(
                "cache='decoded' is image-plane machinery; the text plane's "
                "cross-epoch cache is the packed-slab cache (slab_cache_dir)"
            )
        seq_len = int(seq_len)
        if seq_len < 4:
            raise ValueError("seq_len must be >= 4 (BOS + body + EOS)")
        super().__init__(
            files,
            tokenizer_mod.make_pack_fn(tokenizer, seq_len),
            batch_size,
            shuffle=shuffle,
            seed=seed,
            num_threads=num_threads,
            epochs=epochs,
            prefetch_batches=prefetch_batches,
            verify_crc=verify_crc,
            drop_remainder=drop_remainder,
            max_bad_records=max_bad_records,
            readahead=readahead,
            chunk_records=chunk_records,
            shuffle_buffer=shuffle_buffer,
            cache=cache,
            decode_workers=pack_workers,
            slab_cache_dir=slab_cache_dir,
            store=store,
            prefetch=prefetch,
        )
        self.tokenizer = tokenizer
        self.seq_len = seq_len
        self.pack_ahead = float(pack_ahead)
        self.attention_window = None if attention_window is None else int(attention_window)
        if self.attention_window is not None and block_diffusion is not None:
            raise ValueError("attention_window counts next-token rows; block_diffusion reads its rows under a rule of its own")
        self.scan_restarts = bool(scan_restarts)
        if self.scan_restarts and block_diffusion is not None:
            raise ValueError("scan_restarts counts next-token rows; block_diffusion reads its rows under a rule of its own")
        self.block_diffusion = None
        if block_diffusion is not None:
            self.block_diffusion = dict({"t_min": 1e-3}, **block_diffusion)
            if seq_len % int(self.block_diffusion["block_length"]):
                raise ValueError("seq_len must be a multiple of the diffusion's block_length")

    # -- stage 3: pack assembly ---------------------------------------------

    def __iter__(self):
        from concurrent.futures import ThreadPoolExecutor

        started = time.monotonic()  # until the first batch is handed over
        B, L = self.batch_size, self.seq_len
        out_q = queue.Queue(maxsize=max(1, self.prefetch_batches))
        stop = threading.Event()  # consumer departed
        abort = threading.Event()  # producer died: unblocks reader threads
        _END = object()
        free_q = queue.Queue()  # recycled slab pairs (process mode only)
        pool_cap = max(1, self.prefetch_batches) + 2
        alloc_count = [0]

        produced_c = obs.counter(
            "data_batches_produced_total", help="batches parsed by the input pipeline"
        )
        consumed_c = obs.counter(
            "data_batches_consumed_total", help="batches handed to the training loop"
        )
        depth_g = obs.gauge(
            "data_prefetch_depth", help="parsed batches waiting in the prefetch queue"
        )
        skipped_c = obs.counter(
            "data_records_skipped_total",
            help="undecodable records skipped within the max_bad_records budget",
        )
        read_c = obs.counter(
            "data_producer_read_seconds_total",
            help="seconds spent in shard IO (open + chunk reads)",
        )
        parse_c = obs.counter(
            "data_producer_parse_seconds_total",
            help="seconds the parse pool spent decoding records into batch buffers",
        )
        emit_c = obs.counter(
            "data_producer_emit_seconds_total",
            help="seconds the producer blocked on a full prefetch queue "
            "(backpressure: the consumer is the bottleneck)",
        )
        wait_c = obs.counter(
            "data_consumer_wait_seconds_total",
            help="seconds the consumer waited on an empty prefetch queue "
            "(starvation: the input pipeline is the bottleneck)",
        )
        first_g = obs.gauge(
            "data_first_batch_seconds",
            help="seconds from the newest input iterator's start to its first batch "
            "(producer start, first shard read, first pack or decode)",
        )
        tok_err_c = obs.counter(
            "text_tokenize_errors_total",
            help="records the tokenizer rejected (charged to max_bad_records)",
        )
        trunc_c = obs.counter(
            "text_sequences_truncated_total",
            help="sequences longer than seq_len cut down to the bin capacity",
        )
        tokens_c = obs.counter(
            "text_tokens_packed_total", help="real (non-pad) tokens emitted in packed batches"
        )
        seqs_c = obs.counter(
            "text_sequences_packed_total", help="sequences emitted inside packed batches"
        )
        stall_c = obs.counter(
            "text_pack_stall_seconds_total",
            help="seconds the packer stalled inside the pack stage "
            "(slab-pool waits and injected data.pack_stall faults)",
        )
        eff_g = obs.gauge(
            "text_pack_efficiency",
            help="cumulative real-token fraction of emitted [B, L] slots",
        )
        pad_g = obs.gauge(
            "text_pad_fraction", help="cumulative pad fraction of emitted [B, L] slots"
        )
        blocks_needed_c = obs.counter(
            "flash_blocks_needed_total",
            help="attention blocks the segmented flash kernels compute for the emitted "
            "rows (some query shares a document with some key), per head and pass",
        )
        blocks_dense_c = obs.counter(
            "flash_blocks_dense_total",
            help="blocks of the causal triangle over the emitted rows: what the kernels "
            "computed before they skipped by the packing",
        )
        noise = self.block_diffusion
        #: documents start on multiples of this in their row
        align = int(noise["block_length"]) if noise else 1
        if noise:
            noise_c = obs.counter(
                "data_producer_noise_seconds_total",
                help="seconds the producer spent drawing block-diffusion noise for packed batches",
            )
            masked_c = obs.counter(
                "bd_positions_masked_total", help="positions the block-diffusion noise masked (the loss-bearing ones)")
            real_c = obs.counter(
                "bd_tokens_real_total", help="real (non-pad) tokens of the batches the block-diffusion noise was drawn for")
        emitted_batches = [0]
        grid_steps_c = obs.counter(
            "flash_grid_steps_total",
            help="grid steps the segmented flash kernels take for the emitted rows, per head "
            "and pass: every row of a batch walks as many as the batch's longest list of needed blocks",
        )

        attention_window = self.attention_window
        if attention_window is not None:
            win_c = {
                "blocks_needed": obs.counter(
                    "flash_win_blocks_needed_total",
                    help="attention blocks the windowed flash kernels compute for the emitted rows (some query "
                    "shares a document with some key inside its window), per head and pass"),
                "blocks_dense": obs.counter(
                    "flash_win_blocks_dense_total",
                    help="blocks of the causal triangle over the emitted rows, as flash_blocks_dense_total"),
                "grid_steps": obs.counter(
                    "flash_win_grid_steps_total",
                    help="grid steps the windowed flash kernels take for the emitted rows, per head and pass"),
                "pairs_visible": obs.counter(
                    "flash_win_pairs_visible_total",
                    help="query-key pairs the window rule shows in the emitted rows, per head"),
                "pairs_in_blocks": obs.counter(
                    "flash_win_pairs_in_blocks_total",
                    help="query-key pairs of the blocks the windowed flash kernels compute for the emitted rows, "
                    "per head and pass"),
            }

        scan_c = None
        if self.scan_restarts:
            scan_c = {
                "positions": obs.counter(
                    "ssm_scan_positions_total",
                    help="positions of the emitted rows that a state-space layer's scan walks, per layer and pass"),
                "restarts": obs.counter(
                    "ssm_scan_restarts_total",
                    help="positions of the emitted rows at which a state-space layer's scan starts anew (a document's "
                    "first, a row's first), per layer and pass"),
            }

        # the pack plane forks its workers HERE, before any pipeline thread
        # exists (fork-with-threads is the one mp lifecycle hazard)
        plane = None
        workers, _auto = decode_plane.resolve_workers(self.decode_workers)
        if workers > 0:
            if decode_plane.available():
                plane = decode_plane.DecodePlane(self.parse_fn, workers)
            else:
                logger.warning(
                    "pack_workers=%s requested but fork/shared_memory is "
                    "unavailable here; falling back to the thread pack pool",
                    workers,
                )

        reader_pool = (
            ThreadPoolExecutor(self.readahead, thread_name_prefix="tos-text-reader")
            if self.readahead > 0
            else None
        )

        # packed-row geometry is static — unlike images no bootstrap record
        # is needed to size the cache or the buffers
        cache_box = [None]
        if self.slab_cache_dir is not None:
            try:
                cache_box[0] = slab_cache.SlabCache(
                    self.slab_cache_dir, self.parse_fn.cache_key, (L,), np.int32
                )
            except Exception as e:
                logger.warning("packed-slab cache disabled: %s", e)
        into = self.parse_fn.into

        def _final_put(item):
            # never block forever on a departed consumer: its finally drains
            # the queue and sets stop, so either the put lands or stop shows
            while not stop.is_set():
                try:
                    out_q.put(item, timeout=0.1)
                    return
                except queue.Full:
                    continue

        def _acquire():
            # slabs are pooled (workers hold attachments by name); thread
            # mode emits fresh heap buffers, nothing to recycle
            if plane is None:
                return np.zeros((B, 3, L), np.int32), np.empty((B,), np.int32)
            try:
                pair = free_q.get_nowait()
            except queue.Empty:
                pair = None
            if pair is None:
                if alloc_count[0] < pool_cap:
                    alloc_count[0] += 1
                    pair = plane.new_slab(B, (3, L), np.int32)
                else:
                    # pool exhausted: timed-get until a slab returns or the
                    # consumer departs — this is a genuine pack stall
                    t0 = time.monotonic()
                    while True:
                        if stop.is_set():
                            raise _Stopped()
                        try:
                            pair = free_q.get(timeout=0.1)
                            break
                        except queue.Empty:
                            continue
                    waited = time.monotonic() - t0
                    plane.note_slab_wait(waited)
                    stall_c.inc(waited)
            pair[0][...] = 0  # zero tokens/segments/positions: pad baseline
            return pair

        def producer():
            bad = []  # tokenize errors absorbed so far (within budget)
            window = []  # (record bytes, eff_len) awaiting packing
            window_tokens = 0
            # at least one batch's worth of tokens per window: a mid-stream
            # flush then always yields >= B bins (ceil(tokens/L) >= B) and
            # the carry can never exceed the window it came from
            window_cap = max(B * L, int(self.pack_ahead * B * L))
            emitted_slots = [0]
            emitted_tokens = [0]

            def _absorb(err):
                if len(bad) >= self.max_bad_records:
                    raise err
                bad.append(err)
                skipped_c.inc()
                tok_err_c.inc()
                logger.warning("skipping untokenizable record: %s", err)

            def _emit(batch):
                if chaos.active:
                    chaos.delay("data.producer_delay")
                with obs.span("producer_emit", seconds_total=emit_c):
                    while True:
                        try:
                            out_q.put(batch, timeout=0.5)
                            break
                        except queue.Full:
                            if stop.is_set():
                                raise _Stopped()
                produced_c.inc()
                depth_g.set(out_q.qsize())

            def _cache_hit(rec, eff_len):
                """Serve a sequence's token ids from the packed-slab cache:
                returns (ids, None) on a hit, (None, crc) on a miss to be
                staged after tokenizing, (None, None) when the cache is
                off."""
                cache = cache_box[0]
                if cache is None:
                    return None, None
                crc = zlib.crc32(rec)
                hit = cache.lookup(crc)
                if hit is None:
                    return None, crc
                row, lbl = hit
                if int(lbl) != eff_len:  # stale geometry guard; re-tokenize
                    return None, crc
                return row[:eff_len], None

            def _fill_and_emit(bins):
                """Assemble one batch from packed bins: zeroed buffer, cache
                hits written parent-side, misses tokenized by the pack
                plane (one slot lease per row, the plan as payload) or the
                thread pool, fresh rows staged back into the cache."""
                rows = len(bins)
                buf, labels = _acquire()
                with obs.span("producer_parse", seconds_total=parse_c):
                    if chaos.active:
                        tc = time.monotonic()
                        if chaos.delay("data.pack_stall"):
                            stall_c.inc(time.monotonic() - tc)
                    plans = []  # (slot, plan tuple) for rows with cache misses
                    puts = []  # (crc, slot, offset, eff_len) staged after the round
                    for slot, entries in enumerate(bins):
                        offset = 0
                        plan = []
                        for seg_id, (rec, eff_len) in enumerate(entries, start=1):
                            ids, crc = _cache_hit(rec, eff_len)
                            if ids is not None:
                                tokenizer_mod.write_segment(buf[slot], offset, seg_id, ids)
                            else:
                                plan.append((offset, seg_id, eff_len, rec))
                                if crc is not None:
                                    puts.append((crc, slot, offset, eff_len))
                            offset += -(-eff_len // align) * align
                        labels[slot] = len(entries)
                        if plan:
                            plans.append((slot, tuple(plan)))
                    if plane is not None:
                        if plans:
                            try:
                                failures = plane.run_round(
                                    buf, labels, plans, should_stop=stop.is_set
                                )
                            except decode_plane.Stopped:
                                raise _Stopped()
                            if failures:
                                # token_length already validated every record —
                                # a worker-side encode failure is a real bug,
                                # not a budget event
                                raise failures[0][1]
                    else:
                        list(pool.map(lambda sp: into(sp[1], buf[sp[0]]), plans))
                    cache = cache_box[0]
                    if cache is not None:
                        padded = np.zeros((L,), np.int32)
                        for crc, slot, offset, eff_len in puts:
                            padded[...] = 0
                            padded[:eff_len] = buf[slot, 0, offset : offset + eff_len]
                            cache.put(crc, padded, eff_len)
                n_tokens = sum(n for entries in bins for _, n in entries)
                tokens_c.inc(n_tokens)
                seqs_c.inc(sum(len(entries) for entries in bins))
                emitted_tokens[0] += n_tokens
                emitted_slots[0] += rows * L
                eff = emitted_tokens[0] / emitted_slots[0]
                eff_g.set(eff)
                pad_g.set(1.0 - eff)
                extra = {}
                if noise:
                    with obs.span("producer_noise", seconds_total=noise_c):
                        rng = np.random.default_rng([self.seed, emitted_batches[0]])
                        noised, weights = noise_blocks(
                            buf[:rows, 0], buf[:rows, 1], buf[:rows, 2], align, noise["mask_id"], noise["t_min"], rng)
                        extra = {"noised_tokens": noised, "loss_weights": weights}
                    masked_c.inc(int((weights > 0).sum()))
                    real_c.inc(n_tokens)
                    # the row as that model reads it: the clean copy, then the noised one
                    twice = np.concatenate([buf[:rows, 1], buf[:rows, 1]], axis=1)
                    block = buf[:rows, 2] // align
                    needed, dense, steps = flash_blocks.attended_blocks(
                        twice, np.concatenate([2 * block, 2 * block + 1], axis=1))
                else:
                    # the columns the LM attends (make_loss_fn feeds [:, :-1])
                    attended = buf[:rows, 1, :-1]
                    needed, dense, steps = flash_blocks.attended_blocks(attended)
                    if attention_window is not None:
                        in_window = flash_blocks.attended_blocks(attended, window=attention_window)
                        for name, value in zip(("blocks_needed", "blocks_dense", "grid_steps"), in_window):
                            win_c[name].inc(value)
                        win_c["pairs_visible"].inc(flash_blocks.visible_pairs(attended, attention_window))
                        win_c["pairs_in_blocks"].inc(in_window[0] * flash_blocks.block_pairs(attended))
                    if scan_c is not None:
                        scan_c["positions"].inc(attended.size)
                        scan_c["restarts"].inc(
                            int(np.count_nonzero(attended[:, 1:] != attended[:, :-1])) + attended.shape[0])
                emitted_batches[0] += 1
                blocks_needed_c.inc(needed)
                blocks_dense_c.inc(dense)
                grid_steps_c.inc(steps)
                if plane is not None:
                    # slab views are copied out and the slab returns to the
                    # pool at once (yielded batches are retainable)
                    out = np.array(buf[:rows])
                    free_q.put((buf, labels))
                else:
                    out = buf[:rows]
                _emit(dict({"tokens": out[:, 0], "segment_ids": out[:, 1], "positions": out[:, 2]}, **extra))

            def _flush(final):
                """FFD-pack the window and emit whole batches of B bins.
                Mid-stream, sequences in leftover part-full bins carry into
                the next window (arrival order preserved); at stream end
                the leftovers become one short batch unless
                ``drop_remainder``."""
                nonlocal window, window_tokens
                bins = pack_bins([-(-n // align) * align for _, n in window], L)
                full = (len(bins) // B) * B
                for g in range(0, full, B):
                    _fill_and_emit([[window[i] for i in b] for b in bins[g : g + B]])
                rest = bins[full:]
                if final:
                    if rest and not self.drop_remainder:
                        _fill_and_emit([[window[i] for i in b] for b in rest])
                    # else: short remainder dropped (one static shape)
                    window, window_tokens = [], 0
                else:
                    carry = sorted(i for b in rest for i in b)
                    window = [window[i] for i in carry]
                    window_tokens = sum(n for _, n in window)

            def _epoch_end():
                # pack the epoch's tail into full batches, then seal the
                # staged cache generation — epoch >= 2 reads it back.
                # Part-full leftover bins carry across the epoch boundary
                # (their rows join the next epoch's first commit).
                _flush(final=False)
                if cache_box[0] is not None:
                    cache_box[0].commit()

            try:
                pool_cm = (
                    ThreadPoolExecutor(self.num_threads)
                    if plane is None
                    else _NullPool()
                )
                with pool_cm as pool:
                    for rec in self._record_stream(
                        reader_pool, stop, abort, read_c, on_epoch_end=_epoch_end
                    ):
                        if stop.is_set():
                            return
                        # rolled here, in the producer thread, so the seeded
                        # schedule is independent of reader-thread timing
                        # (chaos call-order determinism) — and identical in
                        # thread and process pack modes: mode-invariant
                        if chaos.active and chaos.fire("data.tokenize_error"):
                            rec = _CHAOS_BAD_RECORD
                        t0 = time.monotonic()
                        try:
                            raw_len = self.tokenizer.token_length(rec)
                        except Exception as e:
                            parse_c.inc(time.monotonic() - t0)
                            _absorb(e)
                            continue
                        parse_c.inc(time.monotonic() - t0)
                        if raw_len > L:
                            trunc_c.inc()
                        window.append((bytes(rec), min(raw_len, L)))
                        window_tokens += min(raw_len, L)
                        if window_tokens >= window_cap:
                            _flush(final=False)
                    if window:
                        _flush(final=True)
            except _Stopped:
                return
            except BaseException as e:  # surfaced on the consuming side
                _final_put(e)
                return
            finally:
                if cache_box[0] is not None:
                    # commit the stream tail's staged rows, then release
                    cache_box[0].commit()
                    cache_box[0].close()
                _final_put(_END)
                abort.set()
                if reader_pool is not None:
                    reader_pool.shutdown(wait=False, cancel_futures=True)

        thread = threading.Thread(target=producer, name="tos-text-producer", daemon=True)
        thread.start()
        try:
            while True:
                with obs.span("batch_wait", seconds_total=wait_c):
                    item = out_q.get()
                if item is _END:
                    return
                if isinstance(item, BaseException):
                    raise item
                if started is not None:
                    first_g.set(time.monotonic() - started)
                    started = None
                consumed_c.inc()
                depth_g.set(out_q.qsize())
                yield item
        finally:
            stop.set()
            # unblock the producer if it is waiting on a full queue (empty()
            # instead of catching Empty: exception classes may already be
            # torn down when a half-consumed generator is GC'd at exit)
            while not out_q.empty():
                out_q.get_nowait()
            if plane is not None:
                # the producer observes stop within one poll interval; only
                # after it is out of the lease protocol is the plane torn
                # down (workers drained, slab pool unlinked)
                thread.join(timeout=10.0)
                plane.close()


class _NullPool:
    """Context stand-in for the thread pool when the pack plane owns the
    parse stage (mirrors the loader's nullcontext use, but typed so the
    ``pool`` name always exists)."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def map(self, fn, items):
        return [fn(it) for it in items]
