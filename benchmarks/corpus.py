"""Seeded corpora for the benchmark's cells, written as TFRecord shards.

One generator per ``corpus.kind`` of a traffic file. Every seed gets the same
multiset of sizes — document lengths are the law's own quantiles, image sizes
cycle through the listed ones — in another order and with other content, so
the work of a run does not depend on the seed. Records go through the
program's public ``tfrecord`` writer: that is the input format its users
write.
"""

import io
import os
import statistics

import numpy as np


def _writer(path):
    from tensorflowonspark_tpu import tfrecord

    return tfrecord.TFRecordWriter(path)


def doc_lengths(law, count):
    """``count`` document lengths in tokens (BOS and EOS included): the
    ``(i + 1/2) / count`` quantiles of the clipped lognormal."""
    if law["law"] != "lognormal":
        raise ValueError("unknown document-length law {!r}".format(law["law"]))
    normal = statistics.NormalDist()
    z = np.array([normal.inv_cdf((i + 0.5) / count) for i in range(count)])
    lengths = np.exp(np.log(law["median"]) + law["sigma"] * z)
    return np.clip(np.rint(lengths), law["min"], law["max"]).astype(np.int64)


def mean_doc_length(law):
    return float(doc_lengths(law, 1000).mean())


def make_text(out_dir, spec, tokens, seed):
    """Raw UTF-8 records of whitespace-separated words (the word tokenizer
    hashes each onto the vocabulary); about ``tokens`` tokens in all."""
    os.makedirs(out_dir)
    count = max(spec["shards"], int(round(tokens / mean_doc_length(spec["doc_tokens"]))))
    rng = np.random.default_rng(seed)
    lengths = rng.permutation(doc_lengths(spec["doc_tokens"], count))
    words = spec["words"]
    if words["law"] != "zipf":
        raise ValueError("unknown word law {!r}".format(words["law"]))
    weights = 1.0 / np.arange(1, words["distinct"] + 1) ** words["exponent"]
    vocabulary = np.array(["w{:x}".format(i) for i in range(words["distinct"])])
    body = np.maximum(lengths - 2, 1)  # the tokenizer adds BOS and EOS
    drawn = vocabulary[rng.choice(words["distinct"], size=int(body.sum()), p=weights / weights.sum())]
    bounds = np.concatenate([[0], np.cumsum(body)])
    per_shard = -(-count // spec["shards"])
    for shard in range(spec["shards"]):
        with _writer(os.path.join(out_dir, "part-{:05d}".format(shard))) as w:
            for i in range(shard * per_shard, min(count, (shard + 1) * per_shard)):
                w.write(" ".join(drawn[bounds[i]:bounds[i + 1]]).encode("utf-8"))
    return {"documents": int(count), "tokens": int(lengths.sum())}


def _jpeg(rng, width, height, quality):
    """A JPEG of about a photograph's size on disk: smooth colour fields
    (low-resolution noise scaled up) under fine grain."""
    from PIL import Image

    coarse = rng.integers(0, 256, (max(height // 24, 2), max(width // 24, 2), 3), dtype=np.uint8)
    img = np.asarray(Image.fromarray(coarse).resize((width, height), Image.BICUBIC), np.int16)
    img = np.clip(img + rng.integers(-24, 25, img.shape, dtype=np.int16), 0, 255).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(img).save(buf, "JPEG", quality=quality)
    return buf.getvalue()


def make_jpeg(out_dir, spec, seed):
    """ImageNet-schema Example records (``image/encoded`` JPEG bytes,
    ``image/class/label``): ``distinct_jpegs`` pictures at the listed stored
    sizes, reused under different labels up to ``images`` records."""
    from tensorflowonspark_tpu import tfrecord

    os.makedirs(out_dir)
    rng = np.random.default_rng(seed)
    sizes = spec["sizes"]
    order = rng.permutation(spec["distinct_jpegs"])
    jpegs = [_jpeg(rng, *sizes[int(i) % len(sizes)], spec["quality"]) for i in order]
    labels = rng.integers(0, 1000, spec["images"])
    per_shard = -(-spec["images"] // spec["shards"])
    for shard in range(spec["shards"]):
        with _writer(os.path.join(out_dir, "part-{:05d}".format(shard))) as w:
            for i in range(shard * per_shard, min(spec["images"], (shard + 1) * per_shard)):
                w.write(tfrecord.encode_example({
                    "image/encoded": [jpegs[i % len(jpegs)]],
                    "image/class/label": [int(labels[i])],
                }))
    return {"images": int(spec["images"]), "jpeg_bytes_mean": float(np.mean([len(j) for j in jpegs]))}
