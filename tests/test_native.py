"""Native C++ loader tests: build, CRC agreement, reader/pool correctness,
corruption detection.  Skipped wholesale when the toolchain can't build the
library (it is an optional fast path; Python is the reference semantics)."""

import subprocess
from pathlib import Path

import numpy as np
import pytest

from distributed_tensorflow_models_tpu.data import tfrecord

NATIVE_DIR = Path(__file__).resolve().parent.parent / "native"


@pytest.fixture(scope="module")
def native():
    r = subprocess.run(
        ["make", "-C", str(NATIVE_DIR)], capture_output=True, text=True
    )
    if r.returncode != 0:
        pytest.skip(f"native build failed: {r.stderr[-500:]}")
    from distributed_tensorflow_models_tpu.data import native_loader

    # The library is not in a checkout (it is built here): an earlier
    # test in this process may have probed for it and cached "absent".
    native_loader._TRIED = False
    if not native_loader.available():
        pytest.skip("native library not loadable")
    return native_loader


def test_native_crc32c_matches_python(native):
    rng = np.random.RandomState(0)
    for n in (0, 1, 7, 8, 9, 64, 1000, 4096):
        data = rng.bytes(n)
        assert native.crc32c(data) == tfrecord.crc32c(data), n
    assert native.crc32c(b"\x00" * 32) == 0x8A9136AA


def test_native_reader_roundtrip(native, tmp_path):
    path = str(tmp_path / "a.tfrecord")
    payloads = [b"hello", b"", b"x" * 100_000, bytes(range(256)) * 7]
    tfrecord.write_records(path, payloads)
    assert native.read_all_records(path) == payloads


def test_native_reader_detects_corruption(native, tmp_path):
    path = tmp_path / "bad.tfrecord"
    tfrecord.write_records(str(path), [b"payload-data-here"])
    raw = bytearray(path.read_bytes())
    raw[16] ^= 0xFF
    path.write_bytes(bytes(raw))
    with pytest.raises(IOError):
        native.read_all_records(str(path))


def test_native_pool_reads_all_shards(native, tmp_path):
    expected = set()
    paths = []
    for s in range(5):
        recs = [f"{s}:{i}".encode() for i in range(200)]
        expected.update(recs)
        p = str(tmp_path / f"shard-{s}")
        tfrecord.write_records(p, recs)
        paths.append(p)
    pool = native.NativeRecordPool(paths, threads=3, capacity=64)
    got = list(pool)
    pool.close()
    assert len(got) == 1000
    assert set(got) == expected


def test_native_pool_close_while_full(native, tmp_path):
    # Workers blocked on a full ring buffer must unblock and join on close.
    p = str(tmp_path / "big")
    tfrecord.write_records(p, [bytes(100) for _ in range(500)])
    pool = native.NativeRecordPool([p] * 4, threads=4, capacity=8)
    for _ in range(10):
        next(pool)
    pool.close()  # must not hang


def test_sharded_iterator_uses_native(native, tmp_path):
    p = str(tmp_path / "s0")
    payloads = [f"r{i}".encode() for i in range(10)]
    tfrecord.write_records(p, payloads)
    it = tfrecord.ShardedRecordIterator([p], shuffle_shards=False, native=True)
    got = [next(iter(it)) for _ in range(10)]
    assert got == payloads


def test_native_throughput_exceeds_python(native, tmp_path):
    """The point of the native path: bulk record framing+CRC beats the
    pure-Python loop by a wide margin (CRC alone is ~1000x)."""
    import time

    rng = np.random.RandomState(1)
    p = str(tmp_path / "perf")
    tfrecord.write_records(p, [rng.bytes(64 * 1024) for _ in range(64)])

    t0 = time.perf_counter()
    native.read_all_records(p)
    t_native = time.perf_counter() - t0

    t0 = time.perf_counter()
    list(tfrecord.read_records(p))
    t_python = time.perf_counter() - t0
    assert t_native < t_python, (t_native, t_python)
