"""The codec's two zstd paths: payloads that span two or more of zstd's jobs
go through its workers, smaller ones keep the one-threaded frame byte for
byte, and either way the bytes at rest are a function of the payload alone.

At level 3 a zstd job is 8 MiB, so the large payloads here are 3 jobs long.
The worker counts asked for never exceed this host's CPUs.
"""

import hashlib
import os
import threading
import zlib

import numpy as np
import pytest

zstandard = pytest.importorskip("zstandard")

from repro import obs  # noqa: E402
from repro.store.objectstore import Codec, ObjectStore  # noqa: E402

JOB = 8 << 20
CORES = len(os.sched_getaffinity(0))


def payload(n: int, seed: int = 0) -> bytes:
    """Compressible, but not trivially: sparse nibbles."""
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 16, n, dtype=np.uint8)
            * rng.integers(0, 2, n, dtype=np.uint8)).tobytes()


@pytest.fixture(scope="module")
def large() -> bytes:
    return payload(2 * JOB + (JOB >> 1))  # 3 jobs: 8, 8 and 4 MiB


@pytest.fixture
def traced():
    tracer = obs.Tracer(enabled=True)
    old = obs.set_tracer(tracer)
    yield tracer
    obs.set_tracer(old)


def cores(monkeypatch, n: int) -> None:
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(n)))


def compress_attrs(tracer):
    return [s.attrs for s in tracer.spans() if s.name == "objects.compress"]


def test_multi_job_payload_round_trips_through_the_store(tmp_path, large, traced):
    store = ObjectStore(tmp_path)
    key, stored = store.put(large)
    assert key == hashlib.sha256(large).hexdigest()
    blob = store._path(key).read_bytes()
    assert len(blob) == stored
    assert zstandard.frame_content_size(blob) == len(large)
    assert store.get(key) == large
    (attrs,) = compress_attrs(traced)
    assert attrs == {"workers": min(CORES, 3) if CORES > 1 else 0,
                     "in_bytes": len(large)}


@pytest.mark.parametrize("workers", [2, 4, 8])
def test_multi_job_frame_is_the_same_for_any_worker_count(
        monkeypatch, large, workers):
    want = zstandard.ZstdCompressor(level=3, threads=2).compress(large)
    n = min(workers, CORES)
    cores(monkeypatch, n)
    assert Codec(backend="zstd").compress(large) == (
        want if n > 1 else zstandard.ZstdCompressor(level=3).compress(large))
    # the frame holds with more workers than the payload has jobs, too
    assert zstandard.ZstdCompressor(level=3, threads=n).compress(large) == want


@pytest.mark.parametrize("n", [0, 1000, 1 << 20, JOB])
def test_payload_under_two_jobs_keeps_the_single_threaded_frame(n, traced):
    p = payload(n, seed=1)
    assert Codec(backend="zstd").compress(p) == \
        zstandard.ZstdCompressor(level=3).compress(p)
    assert compress_attrs(traced) == [{"workers": 0, "in_bytes": n}]


@pytest.mark.parametrize("n,ncores,want", [
    (JOB, 8, 0),              # one job
    (JOB + 1, 8, 2),          # two jobs
    (3 * JOB, 1, 0),          # one CPU: never a single worker
    (3 * JOB, 2, 2),
    (3 * JOB, 8, 3),          # no more workers than jobs
    (193_000_000, 13, 13),    # a ckpt-save version: 24 jobs
])
def test_worker_count_follows_jobs_and_cpus(monkeypatch, n, ncores, want):
    cores(monkeypatch, ncores)
    assert Codec(backend="zstd")._workers(n) == want


@pytest.mark.parametrize("size", ["small", "large"])
def test_compressed_size_is_the_bytes_at_rest(tmp_path, large, size):
    p = large if size == "large" else payload(100_000, seed=2)
    store = ObjectStore(tmp_path)
    key, stored = store.put(p)
    assert store.codec.compressed_size(p) == stored == store.stored_size(key)


def test_concurrent_large_puts_from_four_threads(tmp_path):
    store = ObjectStore(tmp_path)
    ps = [payload(2 * JOB + (1 << 20), seed=10 + i) for i in range(4)]
    keys = [None] * 4
    errors = []
    barrier = threading.Barrier(4)

    def put(i):
        try:
            barrier.wait()
            keys[i], _ = store.put(ps[i])
        except Exception as e:  # pragma: no cover - reported below
            errors.append(e)

    threads = [threading.Thread(target=put, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert [store.get(k) for k in keys] == ps


def test_zlib_backend_is_unchanged(large, traced):
    codec = Codec(backend="zlib")
    blob = codec.compress(large)
    assert blob == zlib.compress(large, 3)
    assert codec.decompress(blob) == large
    assert compress_attrs(traced) == [{"workers": 0, "in_bytes": len(large)}]
