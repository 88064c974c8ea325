"""Python face of the native IO tier (``native/ddl_native.cc``).

The reference's native layer is vendored (Horovod's C++ core, NCCL, MPI —
SURVEY.md §2a); this framework's first-party native code targets the one
place the host must keep up with the accelerator: dataset IO. The C++
library provides crc32c, TFRecord framing/indexing, and a threaded
deterministic fill; this module loads it via ``ctypes`` (no pybind11 in
the TPU-VM image) and carries **bit-identical pure-Python fallbacks** so
every call works — just slower — when a toolchain is unavailable
(``DDL_NATIVE=0`` forces the fallbacks).

Build-on-demand: the first call compiles the library next to the source
with ``g++ -O3``, from ``native/ddl_native.cc`` alone, and names it after
the hash of that source (``libddl_native-<sha256[:16]>.so``). A library is
loaded only under the name of the source in the tree, so a stale or
foreign build product is never trusted — whatever a copy of the tree did
to file times. :func:`native_available` says which path is live.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import struct
import threading
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

import numpy as np

_SRC = Path(__file__).resolve().parents[2] / "native" / "ddl_native.cc"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_tried = False


def _lib_path() -> Path:
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _SRC.with_name(f"libddl_native-{digest}.so")


def _compile(lib_path: Path) -> bool:
    # Per-pid temp name: concurrent first-use builds (launch.py N-process
    # worlds) each write their own file; os.replace publishes atomically.
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC",
        "-o", tmp, str(_SRC), "-lpthread",
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError):
        return False
    os.replace(tmp, lib_path)
    return True


def load_library() -> Optional[ctypes.CDLL]:
    """The CDLL, building it on first use; None when unavailable."""
    global _lib, _lib_tried
    with _lock:
        if _lib is not None or _lib_tried:
            return _lib
        _lib_tried = True
        if os.environ.get("DDL_NATIVE", "1") in ("0", "false", "off"):
            return None
        if not _SRC.exists():
            return None
        lib_path = _lib_path()
        if not lib_path.exists() and not _compile(lib_path):
            return None
        try:
            lib = ctypes.CDLL(str(lib_path))
        except OSError:
            return None
        lib.ddl_crc32c.restype = ctypes.c_uint32
        lib.ddl_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.ddl_masked_crc32c.restype = ctypes.c_uint32
        lib.ddl_masked_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.ddl_tfrecord_write.restype = ctypes.c_int
        lib.ddl_tfrecord_write.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64, ctypes.c_int,
        ]
        lib.ddl_tfrecord_index.restype = ctypes.c_int64
        lib.ddl_tfrecord_index.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_uint64, ctypes.c_int,
        ]
        lib.ddl_fill_uniform_f32.restype = None
        lib.ddl_fill_uniform_f32.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.c_uint64,
            ctypes.c_uint64, ctypes.c_int,
        ]
        _lib = lib
        return _lib


def native_available() -> bool:
    return load_library() is not None


# ------------------------------------------------------------------ crc32c

_CRC_TABLE: Optional[np.ndarray] = None


def _crc_table() -> np.ndarray:
    global _CRC_TABLE
    if _CRC_TABLE is None:
        table = np.zeros(256, np.uint32)
        for i in range(256):
            c = np.uint32(i)
            for _ in range(8):
                c = np.uint32(0x82F63B78) ^ (c >> np.uint32(1)) if c & 1 else c >> np.uint32(1)
            table[i] = c
        _CRC_TABLE = table
    return _CRC_TABLE


def _crc32c_py(data: bytes) -> int:
    table = _crc_table()
    crc = 0xFFFFFFFF
    for b in data:
        crc = int(table[(crc ^ b) & 0xFF]) ^ (crc >> 8)
    return crc ^ 0xFFFFFFFF


def crc32c(data: bytes) -> int:
    """CRC-32C (Castagnoli) of ``data``."""
    lib = load_library()
    if lib is not None:
        return int(lib.ddl_crc32c(data, len(data)))
    return _crc32c_py(data)


def masked_crc32c(data: bytes) -> int:
    """TFRecord's masked CRC of ``data``."""
    lib = load_library()
    if lib is not None:
        return int(lib.ddl_masked_crc32c(data, len(data)))
    crc = _crc32c_py(data)
    return (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF


# ---------------------------------------------------------------- TFRecord


def write_tfrecord(
    path: str, payloads: Sequence[bytes], append: bool = False
) -> None:
    """Write ``payloads`` as a TFRecord file (framing + masked CRCs),
    byte-compatible with ``tf.io.TFRecordWriter`` output."""
    lib = load_library()
    if lib is not None:
        buf = b"".join(payloads)
        lens = (ctypes.c_uint64 * len(payloads))(*map(len, payloads))
        rc = lib.ddl_tfrecord_write(
            str(path).encode(), buf, lens, len(payloads), int(append)
        )
        if rc != 0:
            raise IOError(f"native TFRecord write failed ({rc}) for {path}")
        return
    with open(path, "ab" if append else "wb") as f:
        for payload in payloads:
            header = struct.pack("<Q", len(payload))
            f.write(header)
            f.write(struct.pack("<I", masked_crc32c(header)))
            f.write(payload)
            f.write(struct.pack("<I", masked_crc32c(payload)))


def index_tfrecord(
    path: str, verify: bool = True
) -> Tuple[np.ndarray, np.ndarray]:
    """(payload_offsets, payload_lengths) for every record in ``path``.

    One sequential scan, CRC-verified when ``verify``; the index enables
    seek-based / mmap readers and O(1) record counts afterwards.
    """
    lib = load_library()
    if lib is not None:
        n = lib.ddl_tfrecord_index(str(path).encode(), None, None, 0, int(verify))
        if n == -2:
            raise FileNotFoundError(path)
        if n < 0:
            raise IOError(f"corrupt TFRecord file: {path}")
        offsets = np.zeros(n, np.uint64)
        lengths = np.zeros(n, np.uint64)
        if n:
            n2 = lib.ddl_tfrecord_index(
                str(path).encode(),
                offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                n,
                int(verify),
            )
            if n2 != n:
                raise IOError(f"TFRecord file changed while indexing: {path}")
        return offsets, lengths
    offsets, lengths = [], []
    file_size = os.path.getsize(path)
    with open(path, "rb") as f:
        pos = 0
        while True:
            header = f.read(12)
            if not header:
                break
            if len(header) != 12:
                raise IOError(f"corrupt TFRecord file: {path}")
            (length,) = struct.unpack("<Q", header[:8])
            if length + 4 > file_size - (pos + 12):
                raise IOError(f"corrupt TFRecord length field: {path}")
            if verify:
                (stored,) = struct.unpack("<I", header[8:])
                if masked_crc32c(header[:8]) != stored:
                    raise IOError(f"corrupt TFRecord length CRC: {path}")
                payload = f.read(length)
                footer = f.read(4)
                if len(payload) != length or len(footer) != 4:
                    raise IOError(f"corrupt TFRecord file: {path}")
                if masked_crc32c(payload) != struct.unpack("<I", footer)[0]:
                    raise IOError(f"corrupt TFRecord data CRC: {path}")
            else:
                f.seek(length + 4, os.SEEK_CUR)
            offsets.append(pos + 12)
            lengths.append(length)
            pos += 12 + length + 4
    return np.asarray(offsets, np.uint64), np.asarray(lengths, np.uint64)


def read_tfrecord(path: str, verify: bool = True) -> List[bytes]:
    """All record payloads of ``path`` (index + one pass)."""
    offsets, lengths = index_tfrecord(path, verify=verify)
    out = []
    with open(path, "rb") as f:
        for off, length in zip(offsets.tolist(), lengths.tolist()):
            f.seek(off)
            out.append(f.read(length))
    return out


def count_records(path: str, verify: bool = False) -> int:
    """Number of records in a TFRecord file — one framing scan, no
    payload parsing (fast path for dataset length discovery)."""
    lib = load_library()
    if lib is not None:
        n = lib.ddl_tfrecord_index(str(path).encode(), None, None, 0, int(verify))
        if n == -2:
            raise FileNotFoundError(path)
        if n < 0:
            raise IOError(f"corrupt TFRecord file: {path}")
        return int(n)
    return len(index_tfrecord(path, verify=verify)[0])


# ------------------------------------------------------- deterministic fill


def fill_uniform(
    shape, seed: int, n_threads: Optional[int] = None
) -> np.ndarray:
    """float32 uniform [0, 1] array in splitmix64 counter mode:
    ``out[i] = hash(seed + i)`` — bit-identical between the C++ and numpy
    paths and for every thread count.

    The upper bound is CLOSED: uint32 draws >= 2^32 − 128 round up to
    2^32 under float32, so exactly 1.0 appears with probability ~2^-25
    (both paths round identically, preserving bit-identity). Harmless
    for synthetic-image synthesis; account for it before reusing this as
    a general-purpose [0, 1) generator."""
    n = int(np.prod(shape))
    out = np.empty(n, np.float32)
    lib = load_library()
    if lib is not None:
        lib.ddl_fill_uniform_f32(
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            n,
            ctypes.c_uint64(seed & 0xFFFFFFFFFFFFFFFF),
            int(n_threads or (os.cpu_count() or 1)),
        )
        return out.reshape(shape)
    idx = np.arange(n, dtype=np.uint64) + np.uint64(seed & 0xFFFFFFFFFFFFFFFF)
    with np.errstate(over="ignore"):
        z = idx + np.uint64(0x9E3779B97F4A7C15)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
    bits = (z >> np.uint64(32)).astype(np.uint32)
    out[:] = bits.astype(np.float32) * np.float32(1.0 / 4294967296.0)
    return out.reshape(shape)
