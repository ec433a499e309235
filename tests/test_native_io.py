"""Native C++ TFRecord IO (native/tfrecord_io.cc via ctypes) vs the pure
Python codec — byte-for-byte interchange and corruption detection.

The reference's native IO layer was borrowed (tensorflow-hadoop jar +
TensorFlow's C++ record_reader); ours is in-repo, so it gets the test the
reference never had.
"""

import os

import pytest

from tensorflowonspark_tpu import native_io, tfrecord

pytestmark = pytest.mark.skipif(
    not native_io.available(), reason="native toolchain unavailable"
)


def test_masked_crc_matches_python():
    for data in [b"", b"x", b"hello world", os.urandom(7), os.urandom(8), os.urandom(1000)]:
        assert native_io.masked_crc32c(data) == tfrecord._masked_crc(data)


def test_native_write_python_read(tmp_path):
    recs = [os.urandom(i * 13 + 1) for i in range(40)] + [b""]
    path = str(tmp_path / "native.tfrecord")
    assert native_io.write_records(path, recs) == len(recs)
    assert list(tfrecord.read_records(path)) == recs


def test_python_write_native_read(tmp_path):
    recs = [os.urandom(i * 13 + 1) for i in range(40)]
    path = str(tmp_path / "python.tfrecord")
    with tfrecord.TFRecordWriter(path) as w:
        for r in recs:
            w.write(r)
    assert native_io.read_records(path) == recs


def test_corruption_detected(tmp_path):
    path = str(tmp_path / "good.tfrecord")
    native_io.write_records(path, [b"payload-one", b"payload-two"])
    blob = bytearray(open(path, "rb").read())
    blob[14] ^= 0xFF  # flip a payload byte of record 0
    bad = str(tmp_path / "bad.tfrecord")
    open(bad, "wb").write(bytes(blob))
    with pytest.raises(IOError):
        native_io.read_records(bad)
    # verify_crc=False skips the check and returns the (corrupt) payloads
    assert len(native_io.read_records(bad, verify_crc=False)) == 2


def test_empty_file(tmp_path):
    path = str(tmp_path / "empty.tfrecord")
    open(path, "wb").close()
    assert native_io.read_records(path) == []


def test_tf_interop(tmp_path):
    """The native framing must be readable by TensorFlow itself."""
    tf = pytest.importorskip("tensorflow")
    recs = [b"alpha", b"beta", os.urandom(100)]
    path = str(tmp_path / "interop.tfrecord")
    native_io.write_records(path, recs)
    got = [bytes(x.numpy()) for x in tf.data.TFRecordDataset(path)]
    assert got == recs
    # and the other direction
    path2 = str(tmp_path / "tfwrote.tfrecord")
    with tf.io.TFRecordWriter(path2) as w:
        for r in recs:
            w.write(r)
    assert native_io.read_records(path2) == recs


def test_huge_length_field_rejected(tmp_path):
    """A corrupt 8-byte length near UINT64_MAX must produce a clean error,
    not an out-of-bounds read (the `pos + len` sum would wrap)."""
    import struct

    path = str(tmp_path / "huge.tfrecord")
    payload = b"x" * 10
    header = struct.pack("<Q", 0xFFFFFFFFFFFFFFF0)
    open(path, "wb").write(header + b"\x00" * 4 + payload + b"\x00" * 4)
    with pytest.raises(IOError):
        native_io.read_records(path, verify_crc=True)
    with pytest.raises(IOError):
        native_io.read_records(path, verify_crc=False)


# --------------------------------------------------------------------------
# Native JPEG decode (jpg_* entry points): Pillow is the bit-exactness
# oracle — every geometry the imagenet pipeline uses must produce the exact
# bytes PIL produces, or the byte-identical-stream contract across decode
# modes is broken.

_JPG = pytest.mark.skipif(
    not native_io.jpg_available(), reason="native JPEG decode unavailable"
)


def _checker(w, h, mode="RGB", seed=0):
    """A deterministic test image with enough structure to catch upsampling
    and resampling off-by-ones (gradients + hard edges)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w]
    r = (xx * 255 // max(w - 1, 1)).astype(np.uint8)
    g = (yy * 255 // max(h - 1, 1)).astype(np.uint8)
    b = ((xx // 4 + yy // 4) % 2 * 255).astype(np.uint8)
    arr = np.stack([r, g, b], axis=-1)
    arr ^= rng.integers(0, 32, arr.shape, dtype=np.uint8)
    if mode == "L":
        return arr[..., 0]
    return arr


def _encode_jpg(arr, quality=90, subsampling=-1):
    import io

    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, "JPEG", quality=quality, subsampling=subsampling)
    return buf.getvalue()


def _pil_window(data, box, resize, origin=(0, 0), size=None, flip=False):
    """The PIL oracle for jpg_decode_window's decode→resize→window→flip."""
    import io

    import numpy as np
    from PIL import Image

    img = Image.open(io.BytesIO(data))
    if img.mode != "RGB":
        img = img.convert("RGB")
    r = img.resize(resize, Image.BILINEAR, box=box)
    if size is None:
        size = (resize[1], resize[0])
    ox, oy = origin
    arr = np.asarray(r.crop((ox, oy, ox + size[1], oy + size[0])))
    if flip:
        arr = arr[:, ::-1]
    return arr


@_JPG
def test_jpg_info_matches_pil():
    import io

    from PIL import Image

    for w, h, mode in [(64, 48, "RGB"), (17, 11, "RGB"), (2, 2, "RGB"),
                       (33, 40, "L"), (1, 7, "RGB")]:
        data = _encode_jpg(_checker(w, h, mode))
        assert native_io.jpg_info(data) == Image.open(io.BytesIO(data)).size == (w, h)


@_JPG
def test_jpg_decode_matrix_matches_pil_exactly():
    """Raster decode across codings PIL emits: quality × subsampling ×
    geometry (odd dims, tiny images where libjpeg switches from fancy
    upsampling to replication, grayscale). Identity resize compares the
    raw decode; a torn tolerance here means the two IDCT/upsample paths
    diverged."""
    import numpy as np

    cases = [(64, 48, "RGB"), (17, 11, "RGB"), (5, 3, "RGB"), (2, 2, "RGB"),
             (1, 1, "RGB"), (24, 24, "L"), (7, 16, "L")]
    for quality in (50, 90, 100):
        for subsampling in (0, 1, 2):
            for w, h, mode in cases:
                data = _encode_jpg(_checker(w, h, mode), quality, subsampling)
                out = np.empty((h, w, 3), np.uint8)
                native_io.jpg_decode_window(data, out, (0, 0, w, h), (w, h))
                ref = _pil_window(data, (0, 0, w, h), (w, h))
                assert np.array_equal(out, ref), (
                    "decode mismatch at q={} ss={} {}x{} {}".format(
                        quality, subsampling, w, h, mode))


@_JPG
def test_jpg_decode_window_geometry_matches_pil():
    """The three geometries the imagenet pipeline drives: train fractional
    crop-box + resize + flip, eval full-frame resize + centered window, and
    an off-origin window of an upscale."""
    import numpy as np

    data = _encode_jpg(_checker(61, 43))
    for box, resize, origin, size, flip in [
        ((3.25, 2.5, 50.75, 40.0), (32, 32), (0, 0), None, True),
        ((3.25, 2.5, 50.75, 40.0), (32, 32), (0, 0), None, False),
        ((0, 0, 61, 43), (91, 64), (33, 10), (48, 48), False),
        ((0, 0, 61, 43), (122, 86), (5, 7), (40, 60), True),
    ]:
        if size is None:
            size = (resize[1], resize[0])
        out = np.empty(size + (3,), np.uint8)
        native_io.jpg_decode_window(data, out, box, resize, origin, flip)
        ref = _pil_window(data, box, resize, origin, size, flip)
        assert np.array_equal(out, ref)


@_JPG
def test_jpg_decode_into_strided_slab_rows():
    """A slab slot is a view with padded row stride; the decoder writes
    through strides[0] and must not touch the padding."""
    import numpy as np

    data = _encode_jpg(_checker(30, 20))
    backing = np.full((16, 16 * 3 + 13), 0xAB, np.uint8)
    out = backing[:, :16 * 3].reshape(16, 16, 3)[:12, :10]
    assert out.strides[1] == 3 and out.strides[2] == 1
    native_io.jpg_decode_window(data, out, (0, 0, 30, 20), (14, 16), (2, 3))
    ref = _pil_window(data, (0, 0, 30, 20), (14, 16), (2, 3), (12, 10))
    assert np.array_equal(out, ref)
    assert (backing[:, 16 * 3:] == 0xAB).all()  # padding untouched


@_JPG
def test_jpg_parse_into_matches_pil_parse():
    """End-to-end rng protocol: make_parse_fn's native ``into`` must land
    byte-identical pixels to the PIL ``parse`` for the same record — train
    (crop-box draws then flip draw) and eval (aspect resize + center crop)."""
    import numpy as np

    from tensorflowonspark_tpu.data import imagenet

    for is_training in (True, False):
        parse = imagenet.make_parse_fn(
            is_training, image_size=32, seed=7, raw_uint8=True)
        for i in range(6):
            rec = imagenet.encode_example(_checker(57 + 3 * i, 49 + 2 * i, seed=i), i)
            ref_img, ref_lbl = parse(rec)
            out = np.empty((32, 32, 3), np.uint8)
            lbl, used_native = parse.into(rec, out)
            assert used_native, "native path unexpectedly fell back"
            assert lbl == ref_lbl
            assert np.array_equal(out, ref_img)


@_JPG
def test_jpg_corrupt_and_truncated_raise_jpegerror():
    import numpy as np

    data = _encode_jpg(_checker(32, 24))
    out = np.empty((24, 32, 3), np.uint8)
    for bad in [b"", b"\xff\xd8", data[: len(data) // 2], b"not a jpeg at all",
                data[:2] + b"\x00" * 64]:
        with pytest.raises((native_io.JpegError, ValueError)):
            native_io.jpg_info(bad)
        with pytest.raises((native_io.JpegError, ValueError)):
            native_io.jpg_decode_window(bad, out, (0, 0, 32, 24), (32, 24))


@_JPG
def test_jpg_header_fuzz_never_crashes():
    """The sanitizer-leg workload: truncations at every prefix, trailing
    garbage, and lying segment-length fields must either decode cleanly or
    raise JpegError — never read out of bounds (ASan would abort)."""
    import numpy as np

    data = _encode_jpg(_checker(40, 30), quality=75)
    out = np.empty((30, 40, 3), np.uint8)

    def attempt(blob):
        try:
            native_io.jpg_info(blob)
            native_io.jpg_decode_window(blob, out, (0, 0, 40, 30), (40, 30))
        except native_io.JpegError:
            pass

    for cut in range(0, len(data), 3):      # truncated streams
        attempt(data[:cut])
    attempt(data + b"\xde\xad" * 32)        # overlong: trailing garbage
    mutated = 0
    for i in range(len(data) - 4):          # lying segment lengths
        if data[i] == 0xFF and data[i + 1] not in (0x00, 0xD8, 0xD9):
            for fake in (b"\x00\x00", b"\x00\x01", b"\xff\xff"):
                attempt(data[: i + 2] + fake + data[i + 4:])
            mutated += 1
    assert mutated > 0


def test_build_info_reports_jpeg_variant():
    """tfr_build_info() pins which backend the Makefile probe selected; the
    string is surfaced in BENCH JSON so perf numbers carry their decoder."""
    import re

    info = native_io.build_info()
    if not native_io.load_library().tfr_has_jpeg:
        assert info is None
        return
    assert re.fullmatch(
        r"tfrecord_io jpeg=(libjpeg-turbo api=\d+|scalar) src=[0-9a-f]{12}", info
    )
    assert info.endswith("src=" + native_io._source_id())


def test_stale_in_tree_library_is_rebuilt(tmp_path, monkeypatch):
    """A git-ignored .so left in the tree from other source must not be
    trusted: the loader compares its ``src=`` fingerprint with the checked
    out ``tfrecord_io.cc`` and rebuilds on mismatch."""
    import shutil

    if shutil.which("g++") is None or shutil.which("make") is None:
        pytest.skip("no toolchain to rebuild with")
    native = tmp_path / "native"
    native.mkdir()
    src_dir = os.path.join(os.path.dirname(__file__), "..", "native")
    for name in ("Makefile", "tfrecord_io.cc"):
        shutil.copy(os.path.join(src_dir, name), native / name)
    lib = native / "libtfrecord_io.so"
    lib.write_bytes(b"\x7fELF stale src=000000000000")
    monkeypatch.setattr(native_io, "_NATIVE_DIR", str(native))
    monkeypatch.setattr(native_io, "_LIB_PATH", str(lib))
    source_id = native_io._source_id()
    assert not native_io._is_current(source_id)
    assert native_io._build_if_stale(source_id)
    assert native_io._is_current(source_id)
    assert lib.stat().st_size > 10_000


def test_decode_env_var_vetoes_native_path(monkeypatch):
    monkeypatch.setenv(native_io.DECODE_ENV_VAR, "0")
    assert not native_io.jpg_available()
    monkeypatch.delenv(native_io.DECODE_ENV_VAR)
    assert native_io.jpg_available() == bool(native_io.load_library().tfr_has_jpeg)


def test_stale_library_without_jpeg_falls_back(tmp_path):
    """A prebuilt .so that predates the jpg_* entry points (-DTFR_OMIT_JPEG)
    must keep serving record IO while image decode falls back to PIL with
    identical pixels — the stale-.so half of the fallback contract."""
    import shutil
    import subprocess
    import sys
    import textwrap

    if shutil.which("g++") is None:
        pytest.skip("no compiler to build the stale variant")
    src = os.path.join(os.path.dirname(__file__), "..", "native", "tfrecord_io.cc")
    stale = str(tmp_path / "libtfrecord_io_stale.so")
    subprocess.run(
        ["g++", "-O1", "-fPIC", "-shared", "-std=c++17", "-DTFR_OMIT_JPEG",
         "-o", stale, src],
        check=True, capture_output=True, timeout=120)
    prog = textwrap.dedent("""
        import numpy as np
        from tensorflowonspark_tpu import native_io
        from tensorflowonspark_tpu.data import imagenet
        assert native_io.available()
        assert not native_io.load_library().tfr_has_jpeg
        assert not native_io.jpg_available()
        assert native_io.build_info() is None
        parse = imagenet.make_parse_fn(True, image_size=16, seed=3, raw_uint8=True)
        rec = imagenet.encode_example(
            np.arange(31 * 27 * 3, dtype=np.uint8).reshape(27, 31, 3), 5)
        ref_img, ref_lbl = parse(rec)
        out = np.empty((16, 16, 3), np.uint8)
        lbl, used_native = parse.into(rec, out)
        assert not used_native and lbl == ref_lbl
        assert np.array_equal(out, ref_img)
        import tempfile, os as _os
        shard = _os.path.join(tempfile.mkdtemp(), "s.tfrecord")
        native_io.write_records(shard, [rec])
        assert native_io.read_records(shard) == [rec]
        print("STALE-OK")
    """)
    env = dict(os.environ, TOS_NATIVE_LIB=stale)
    env.pop("TOS_NATIVE_DECODE", None)
    r = subprocess.run(
        [sys.executable, "-c", prog], env=env, capture_output=True,
        text=True, timeout=120, cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert r.returncode == 0, r.stderr
    assert "STALE-OK" in r.stdout
