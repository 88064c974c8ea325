"""Native IO tier tests (native/ddl_native.cc + ctypes bindings).

The C++ path and the pure-Python fallback must be byte-identical, and
both must interoperate with TensorFlow's own TFRecord/Example readers —
the compatibility contract that lets the framework's writer feed the
tf.data pipeline (``data/imagenet.py``).
"""

import numpy as np
import pytest

import distributeddeeplearning_tpu.native as native
from distributeddeeplearning_tpu.native import (
    count_records,
    crc32c,
    fill_uniform,
    index_tfrecord,
    masked_crc32c,
    read_tfrecord,
    write_tfrecord,
)
from distributeddeeplearning_tpu.native.example_proto import (
    encode_example,
    parse_example,
)

PAYLOADS = [b"hello tfrecord", b"", b"x" * 1000, bytes(range(256))]


def test_crc32c_known_answer():
    # RFC 3720 check value for CRC-32C
    assert crc32c(b"123456789") == 0xE3069283
    assert crc32c(b"") == 0
    assert native._crc32c_py(b"123456789") == 0xE3069283


def test_native_library_builds():
    """g++ is in the image (SURVEY/environment contract) — the native
    build must actually succeed here, not silently fall back."""
    assert native.native_available(), "the native library failed to build"
    # named after the content of the source it was built from: freshness
    # does not depend on file times a copy of the tree may have reset
    import hashlib

    digest = hashlib.sha256(native._SRC.read_bytes()).hexdigest()[:16]
    assert native._lib_path().name == f"libddl_native-{digest}.so"
    assert native._lib_path().exists()


def test_python_fallback_matches_native(tmp_path, monkeypatch):
    if not native.native_available():
        pytest.skip("no native lib to compare against")
    native_file = tmp_path / "native.tfrecord"
    write_tfrecord(str(native_file), PAYLOADS)
    # force the pure-Python path
    monkeypatch.setattr(native, "load_library", lambda: None)
    py_file = tmp_path / "py.tfrecord"
    write_tfrecord(str(py_file), PAYLOADS)
    assert native_file.read_bytes() == py_file.read_bytes()
    assert crc32c(b"123456789") == 0xE3069283  # fallback crc
    offs, lens = index_tfrecord(str(native_file))  # fallback indexer
    assert list(lens) == [len(p) for p in PAYLOADS]
    assert read_tfrecord(str(py_file)) == PAYLOADS


def test_roundtrip_and_index(tmp_path):
    path = tmp_path / "a.tfrecord"
    write_tfrecord(str(path), PAYLOADS)
    assert read_tfrecord(str(path)) == PAYLOADS
    assert count_records(str(path)) == len(PAYLOADS)
    offsets, lengths = index_tfrecord(str(path))
    assert list(lengths) == [len(p) for p in PAYLOADS]
    # offsets point at the payloads themselves
    blob = path.read_bytes()
    for payload, off, length in zip(PAYLOADS, offsets, lengths):
        assert blob[int(off) : int(off) + int(length)] == payload
    # append mode
    write_tfrecord(str(path), [b"tail"], append=True)
    assert read_tfrecord(str(path))[-1] == b"tail"


def test_corruption_detected(tmp_path):
    path = tmp_path / "bad.tfrecord"
    write_tfrecord(str(path), PAYLOADS)
    blob = bytearray(path.read_bytes())
    blob[14] ^= 0xFF  # flip a payload byte of record 0
    path.write_bytes(bytes(blob))
    with pytest.raises(IOError):
        index_tfrecord(str(path), verify=True)
    # verify=False skips CRCs and still walks the framing
    assert count_records(str(path), verify=False) == len(PAYLOADS)


def test_tf_reads_native_file(tmp_path):
    tf = pytest.importorskip("tensorflow")
    path = tmp_path / "native.tfrecord"
    write_tfrecord(str(path), PAYLOADS)
    got = [bytes(r.numpy()) for r in tf.data.TFRecordDataset(str(path))]
    assert got == PAYLOADS


def test_native_reads_tf_file(tmp_path):
    tf = pytest.importorskip("tensorflow")
    path = tmp_path / "tf.tfrecord"
    with tf.io.TFRecordWriter(str(path)) as w:
        for p in PAYLOADS:
            w.write(p)
    assert read_tfrecord(str(path), verify=True) == PAYLOADS


def test_example_codec_roundtrip():
    ex = {"image/encoded": b"\x89JPGDATA", "image/class/label": [417]}
    payload = encode_example(ex)
    assert parse_example(payload) == ex


def test_example_codec_vs_tensorflow():
    tf = pytest.importorskip("tensorflow")
    payload = encode_example(
        {"image/encoded": b"jpegbytes", "image/class/label": [7]}
    )
    feats = tf.io.parse_single_example(
        payload,
        {
            "image/encoded": tf.io.FixedLenFeature([], tf.string),
            "image/class/label": tf.io.FixedLenFeature([], tf.int64),
        },
    )
    assert bytes(feats["image/encoded"].numpy()) == b"jpegbytes"
    assert int(feats["image/class/label"].numpy()) == 7
    # and the inverse: parse TF's own serialization
    ex = tf.train.Example(
        features=tf.train.Features(
            feature={
                "image/encoded": tf.train.Feature(
                    bytes_list=tf.train.BytesList(value=[b"abc"])
                ),
                "image/class/label": tf.train.Feature(
                    int64_list=tf.train.Int64List(value=[99])
                ),
            }
        )
    )
    parsed = parse_example(ex.SerializeToString())
    assert parsed["image/encoded"] == b"abc"
    assert parsed["image/class/label"] == [99]


def test_fill_uniform_deterministic(monkeypatch):
    a = fill_uniform((64, 7), seed=123, n_threads=1)
    b = fill_uniform((64, 7), seed=123, n_threads=4)
    np.testing.assert_array_equal(a, b)  # thread-count invariant
    assert a.shape == (64, 7) and a.dtype == np.float32
    assert 0.0 <= a.min() and a.max() < 1.0
    c = fill_uniform((64, 7), seed=124, n_threads=1)
    assert np.abs(a - c).max() > 0
    # numpy fallback is bit-identical to the C++ path
    monkeypatch.setattr(native, "load_library", lambda: None)
    d = fill_uniform((64, 7), seed=123)
    np.testing.assert_array_equal(a, d)
