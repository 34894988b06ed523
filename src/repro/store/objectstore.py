"""Content-addressed object store (the physical layer under the version store).

Blobs are compressed through one :class:`Codec` and stored under their
sha256; writes are atomic (tmp + rename) so a preempted checkpoint save never
corrupts the store — the object either exists fully or not at all.  Dedup
falls out of content addressing: committing an identical shard twice stores
one blob.

``zstandard`` is an *optional* dependency: when it is absent the codec falls
back to stdlib ``zlib`` transparently.  Decompression dispatches on the frame
magic, so a store written with zstd stays readable as long as ``zstandard``
is installed, and zlib-written stores are readable everywhere.  (The on-disk
``.zst`` suffix is kept for layout stability regardless of backend — blob
contents are self-describing.)
"""

from __future__ import annotations

import hashlib
import os
import tempfile
import threading
import zlib
from pathlib import Path
from typing import Dict, Optional

from ..obs.tracer import span as _span

try:  # optional dependency — stdlib zlib fallback below
    import zstandard
except ImportError:  # pragma: no cover - exercised via Codec(backend="zlib")
    zstandard = None

_ZSTD_MAGIC = b"\x28\xb5\x2f\xfd"


class Codec:
    """The one compression codec every store byte goes through.

    All compression in the store layer — object payloads *and* the Δ
    measurements of ``VersionStore.build_cost_graph`` — routes through this
    class, so measured storage costs always equal bytes actually at rest.
    """

    def __init__(self, level: int = 3, *, backend: Optional[str] = None) -> None:
        if backend is None:
            backend = "zstd" if zstandard is not None else "zlib"
        if backend == "zstd" and zstandard is None:
            raise RuntimeError("zstandard requested but not installed")
        if backend not in ("zstd", "zlib"):
            raise ValueError(f"unknown codec backend {backend!r}")
        self.backend = backend
        self.level = level
        # zstd contexts are not thread-safe: each thread keeps its own
        self._local = threading.local()

    def _zstd(self) -> threading.local:
        ctx = self._local
        if not hasattr(ctx, "c"):
            ctx.c = zstandard.ZstdCompressor(level=self.level)
            ctx.d = zstandard.ZstdDecompressor()
            ctx.mt, ctx.workers = None, 0
        return ctx

    def _workers(self, n: int) -> int:
        """zstd workers for an ``n``-byte payload: one per zstd job it spans,
        up to the CPUs this process may run on, or 0 (the one-threaded
        frame) below two jobs or two CPUs; never 1, which is slower than 0.

        The job is zstd's own default: 4 windows and at least 1 MiB
        (``ZSTDMT_computeTargetJobLog``, long-distance matching off), the
        window as zstd picks it for this level and source size.  At level 3
        the window is 2 MiB for any source of 2 MiB or more, so a job is
        8 MiB and payloads over 8 MiB take workers.  zstd's multi-threaded
        frame does not depend on the worker count, so the bytes at rest
        stay a function of the payload alone."""
        window_log = zstandard.ZstdCompressionParameters.from_level(
            self.level, source_size=n).window_log
        jobs = -(-n // (1 << max(20, window_log + 2)))
        if jobs < 2:
            return 0
        workers = min(len(os.sched_getaffinity(0)), jobs)
        return workers if workers > 1 else 0

    def _compressor(self, workers: int):
        ctx = self._zstd()
        if not workers:
            return ctx.c
        if ctx.workers != workers:
            # one multi-threaded context a thread: its workers live as long
            # as it does, and it is rebuilt only when the count changes
            ctx.mt = zstandard.ZstdCompressor(level=self.level, threads=workers)
            ctx.workers = workers
        return ctx.mt

    def compress(self, payload: bytes | memoryview) -> bytes:
        with _span("objects.compress") as sp:
            zstd = self.backend == "zstd"
            workers = self._workers(len(payload)) if zstd else 0
            if sp:
                sp.set(workers=workers, in_bytes=len(payload))
            if zstd:
                return self._compressor(workers).compress(payload)
            # zstd levels reach 22; zlib tops out at 9
            return zlib.compress(payload, min(self.level, 9))

    def decompress(self, blob: bytes) -> bytes:
        with _span("objects.decompress"):
            # dispatch on frame magic so mixed-backend stores keep working
            if blob[:4] == _ZSTD_MAGIC:
                if zstandard is None:
                    raise RuntimeError(
                        "blob was written with zstd but zstandard is not "
                        "installed"
                    )
                return self._zstd().d.decompress(blob)
            return zlib.decompress(blob)

    def compressed_size(self, payload: bytes | memoryview) -> int:
        """Bytes this payload would occupy at rest (the measured Δ)."""
        return len(self.compress(payload))


class ObjectStore:
    def __init__(
        self,
        root: str | Path,
        *,
        zstd_level: int = 3,
        codec: Optional[Codec] = None,
    ) -> None:
        self.root = Path(root)
        (self.root / "objects").mkdir(parents=True, exist_ok=True)
        self.codec = codec or Codec(level=zstd_level)

    def _path(self, key: str) -> Path:
        return self.root / "objects" / f"{key[:2]}" / f"{key[2:]}.zst"

    def put(self, payload: bytes | memoryview) -> tuple[str, int]:
        """Store a blob; returns (key, stored_bytes)."""
        with _span("hash.sha256"):
            key = hashlib.sha256(payload).hexdigest()
        path = self._path(key)
        if path.exists():
            return key, path.stat().st_size
        path.parent.mkdir(parents=True, exist_ok=True)
        compressed = self.codec.compress(payload)
        with _span("objects.write"):
            fd, tmp = tempfile.mkstemp(dir=str(path.parent), suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(compressed)
                os.replace(tmp, path)  # atomic on POSIX
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        return key, len(compressed)

    def get(self, key: str) -> bytes:
        with _span("objects.read"):
            blob = self._path(key).read_bytes()
        return self.codec.decompress(blob)

    def exists(self, key: str) -> bool:
        return self._path(key).exists()

    def stored_size(self, key: str) -> int:
        return self._path(key).stat().st_size

    def delete(self, key: str) -> None:
        p = self._path(key)
        if p.exists():
            p.unlink()

    def keys(self):
        for sub in (self.root / "objects").iterdir():
            if sub.is_dir():
                for f in sub.iterdir():
                    if f.suffix == ".zst":
                        yield sub.name + f.stem

    def total_bytes(self) -> int:
        return sum(self._path(k).stat().st_size for k in self.keys())
