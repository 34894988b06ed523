"""VersionStore — the paper's system, end to end.

Tracks a *version graph* (derivation DAG from commits/branches/merges) and a
*storage graph* (what is physically stored: full objects and deltas), keeps
the measured Δ/Φ matrices, and re-optimizes the storage graph on demand
against a declarative :class:`~repro.core.spec.OptimizeSpec` (``repack``;
string solver names remain as a deprecated shim).  Named branches/tags and
the git-shaped verb set live in the
:class:`~repro.store.repository.Repository` facade; the ref table is
persisted here, in the same atomic metadata file as the version metas.

Commit path (online): a new version is stored as a delta against its first
parent's payload when that is smaller than storing it whole — a cheap local
rule; the *global* storage graph is what ``repack`` optimizes offline,
exactly mirroring Git's commit-then-`git repack` split that the paper
analyzes (§4.4, Appendix A).

Checkout path (the recreation layer): every checkout routes through the
:class:`~repro.store.materializer.Materializer` — a ``CheckoutPlanner`` that
compiles one or many requested vids into a topologically ordered decode plan
(shared storage-chain prefixes decoded exactly once), executed through the
fused device-resident delta pipeline and a byte-budgeted LRU
``MaterializationCache`` of FlatTrees.  Each cache entry is tagged with its
vid's *decode-chain* fingerprint (:meth:`VersionStore.chain_fingerprint`),
so a commit (which appends ``(vid, stored_base, object_key)`` triples but
rewrites none) keeps every warm entry alive and interleaved save+serve
traffic never goes cold; ``repack`` rewrites chains and purges the cache
wholesale, and a stale tree can never be served.  Each commit writes its
tree through into the cache under the new vid (once it is durable, and only
if it fits the budget), so the next commit's parent is a hit, not a decode
from disk.  ``checkout`` serves hot versions from memory; ``checkout_many``
batches k checkouts into one plan, bit-identical to k sequential calls but
strictly cheaper on chain-sharing batches.  The cache budget is the
``cache_budget_bytes`` constructor knob (default 256 MiB; 0 disables caching
while keeping within-batch prefix sharing), and
``repack(use_access_frequencies=True)`` prefetches the hottest versions back
into the cache after rewriting storage.

Concurrency: the store is single-writer / multi-reader.  Checkouts may run
from several threads at once — the materialization cache takes its own lock,
and access-count bumps, vid allocation and metadata writes are guarded by
the store lock.  Mutating operations (``commit``, ``repack``,
``gc``, ref writes) must stay confined to one writer at a time; ``commit``
may run concurrently with readers (it only appends), while ``repack``/``gc``
require exclusive access (the service tier enforces both with a
reader-writer lock).

Access counts are the workload signal for frequency-aware repacking; they
are flushed to the metadata file every ``access_flush_every`` checkouts and
on ``repack``/``close``, so counts survive a reload.

Incremental Δ/Φ measurement: every measured matrix entry is persisted in the
msgpack metadata keyed by ``(src, dst)`` together with the content
fingerprints of both endpoint payloads.  ``build_cost_graph`` only re-measures
entries whose endpoints changed (version contents are immutable, so in
practice only pairs touching versions committed since the last measurement) —
``repack`` no longer re-checkouts and re-compresses every version on every
call.  All compression routes through the ObjectStore's :class:`Codec`, so
measured Δ equals bytes actually stored.

All metadata lives in one msgpack file (atomic rewrite); payloads live in the
content-addressed :class:`ObjectStore`.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import tempfile
import threading
import time
import warnings
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import msgpack

from ..core import (
    OptimizeSpec,
    StorageSolution,
    VersionGraph,
    optimize,
    spec_from_solver,
)
from .delta import (
    FlatTree,
    RecreationCostModel,
    encode_delta,
    encode_full,
    flatten_payload,
)
from ..obs.tracer import span as _span
from .materializer import Materializer
from .objectstore import ObjectStore


@dataclasses.dataclass
class VersionMeta:
    vid: int
    parents: List[int]                  # derivation parents (version graph)
    message: str
    created_at: float
    raw_bytes: int                      # uncompressed payload size
    # physical storage: either a full object or a delta from `stored_base`
    stored_base: Optional[int] = None   # None => materialized
    object_key: str = ""
    stored_bytes: int = 0
    phi: float = 0.0                    # recreation cost of this edge
    access_count: int = 0
    content_fp: str = ""                # sha256 of the full (uncompressed) payload


class _PayloadProvider:
    """Lazy edge-payload encoder backing ``repack``.

    Maps ``(src, dst)`` to ``(encoded_payload, stats)`` — a full encoding for
    ``src == 0``, a delta otherwise — materializing checkouts and encodings
    on first use only.  ``repack`` therefore encodes just the n−1 edges the
    solver actually chose, not every measured candidate pair.

    FlatTree materialization goes through the store's shared
    :class:`~repro.store.materializer.MaterializationCache` (no private
    per-provider tree dicts), so a repack's checkouts and the serving path
    reuse the same byte-budgeted cache.
    """

    def __init__(self, store: "VersionStore") -> None:
        self._store = store
        self._memo: Dict[Tuple[int, int], Tuple[bytes, Dict]] = {}

    def flat(self, vid: int) -> FlatTree:
        return self._store._checkout_flat(vid)

    def full_payload(self, vid: int) -> bytes:
        return self[(0, vid)][0]

    def __getitem__(self, key: Tuple[int, int]) -> Tuple[bytes, Dict]:
        if key not in self._memo:
            src, dst = key
            if src == 0:
                self._memo[key] = (encode_full(self.flat(dst)), {})
            else:
                # one batched plan: the pair's shared chain prefix is decoded
                # once even when the cache can't hold it (tiny/zero budgets)
                src_t, dst_t = self._store.materializer.checkout_many(
                    [src, dst]
                )
                self._memo[key] = encode_delta(src_t, dst_t)
        return self._memo[key]


class VersionStore:
    def __init__(
        self,
        root: str | Path,
        *,
        cost_model: Optional[RecreationCostModel] = None,
        delta_hops: int = 3,
        cache_budget_bytes: int = 256 << 20,
        access_flush_every: int = 64,
        prefetch_hot_k: int = 8,
    ) -> None:
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.objects = ObjectStore(self.root)
        self.cost_model = cost_model or RecreationCostModel()
        self.delta_hops = delta_hops
        self.versions: Dict[int, VersionMeta] = {}
        self._next_vid = 1
        # guards hot state shared with service-tier reader threads: access
        # counts, vid allocation, and metadata writes
        self._lock = threading.RLock()
        # measured Δ entries: (src, dst) -> {sfp, dfp, delta, payload_len,
        # changed_blocks}; persisted in the msgpack metadata so repack only
        # re-measures pairs whose endpoints changed
        self._edge_cache: Dict[Tuple[int, int], Dict[str, Any]] = {}
        self.last_measured_edges = 0
        # named refs (branches are mutable pointers, tags immutable) plus the
        # current branch; owned by the Repository facade, persisted in the
        # msgpack metadata so they survive a close/reopen like version metas
        self.refs: Dict[str, Any] = {"branches": {}, "tags": {}, "head": "main"}
        # recreation layer: planner + byte-budgeted FlatTree LRU
        self.materializer = Materializer(self, budget_bytes=cache_budget_bytes)
        self.access_flush_every = access_flush_every
        self.prefetch_hot_k = prefetch_hot_k
        self._unflushed_accesses = 0
        # record of the last repack's spec + outcome, persisted with the
        # metadata: fsck re-validates the recorded constraints against the
        # *current* storage graph (a post-repack mutation that silently
        # violates an agreed bound is a finding, not a crash)
        self.last_repack: Optional[Dict[str, Any]] = None
        # optional live (C, R) sampler (repro.obs.tradeoff.TradeoffMonitor):
        # when attached, commit/repack notify it.  Sampling is O(n) per
        # event, so the service tier attaches it — not store-building loops.
        self.tradeoff_monitor: Optional[Any] = None
        self._meta_path = self.root / "meta.msgpack"
        if self._meta_path.exists():
            self._load_meta()

    # ------------------------------------------------------------- commits
    def commit(
        self,
        payload: Any,
        *,
        parents: Sequence[int] = (),
        message: str = "",
        update_branch: Optional[str] = None,
    ) -> int:
        """Add a version; returns its id.  ``payload`` is any pytree.

        ``update_branch`` points the named branch ref at the new version in
        the same atomic metadata write as the commit itself (used by the
        Repository facade — one rewrite per commit, never two)."""
        with _span("store.commit") as csp:
            flat = flatten_payload(payload)
            raw = sum(a.nbytes for a in flat.values())
            with self._lock:
                vid = self._next_vid
                self._next_vid += 1

            full_payload = encode_full(flat)
            stored_base = None
            best_obj = full_payload
            best_stats = None
            if parents:
                with _span("store.parent", vid=parents[0]):
                    base_flat = self._checkout_flat(parents[0])
                delta_payload, stats = encode_delta(base_flat, flat)
                if len(delta_payload) < len(full_payload):
                    stored_base = parents[0]
                    best_obj = delta_payload
                    best_stats = stats
            key, stored = self.objects.put(best_obj)
            if stored_base is None:
                phi = self.cost_model.phi_full(stored, raw)
            else:
                phi = self.cost_model.phi_delta(
                    stored, len(best_obj), best_stats["changed_blocks"]
                )
            with self._lock:
                with _span("hash.sha256"):
                    content_fp = hashlib.sha256(full_payload).hexdigest()
                self.versions[vid] = VersionMeta(
                    vid=vid,
                    parents=list(parents),
                    message=message,
                    created_at=time.time(),
                    raw_bytes=raw,
                    stored_base=stored_base,
                    object_key=key,
                    stored_bytes=stored,
                    phi=phi,
                    content_fp=content_fp,
                )
                # a commit only *appends* a (vid, stored_base, object_key)
                # triple: every existing decode chain is untouched, so warm
                # cache entries stay valid
                if update_branch is not None:
                    self.refs["branches"][update_branch] = vid
                self._save_meta()
            # the committed tree is the next commit's parent: cache it now,
            # off the store lock, so that commit does not decode it from disk
            with _span("store.keep") as ksp:
                kept = self.materializer.keep(vid, flat, payload)
                if ksp:
                    ksp.set(kept=int(kept))
            if csp:
                csp.set(
                    vid=vid,
                    raw_bytes=raw,
                    stored_bytes=stored,
                    encoding="full" if stored_base is None else "delta",
                )
        mon = self.tradeoff_monitor
        if mon is not None:
            mon.on_commit(vid)
        return vid

    # ------------------------------------------------------------ checkout
    def chain_fingerprint(self, vid: int) -> str:
        """Fingerprint of ``vid``'s decode chain: a rolling hash over the
        ``(vid, stored_base, object_key)`` triples from ``vid`` down to its
        full object.  This is the append-aware cache tag — a commit adds new
        triples but never rewrites existing ones, so every existing chain
        fingerprint survives commits; a chain rewrite (repack, or a direct
        metadata edit) changes it and the tagged cache entry dies on its
        next lookup.  Recomputed per call — deliberately not memoized, so
        even out-of-band ``stored_base``/``object_key`` edits are caught —
        and the walk is bounded, so a corrupted cycle raises instead of
        looping.  Holds the store lock: reader-pool threads fingerprint
        chains while the writer thread mutates ``versions``, and the walk
        must observe either the pre- or post-mutation graph, never a mix."""
        with self._lock:
            h = hashlib.sha256()
            v: Optional[int] = vid
            hops = 0
            while v is not None:
                meta = self.versions[v]
                h.update(
                    f"{v}:{meta.stored_base}:{meta.object_key};".encode()
                )
                v = meta.stored_base
                hops += 1
                if hops > len(self.versions):
                    raise RuntimeError("storage graph cycle")
            return h.hexdigest()

    def checkout(self, vid: int) -> FlatTree:
        """Recreate a version through the materialization layer."""
        return self.checkout_many([vid])[0]

    def checkout_many(self, vids: Sequence[int]) -> List[FlatTree]:
        """Batch checkout: one plan, shared chain prefixes decoded once.

        Bit-identical to ``[checkout(v) for v in vids]``.  Returned arrays
        are shared with the cache and read-only; copy before mutating.
        """
        out = self.materializer.checkout_many(vids)
        # bump only after success: a KeyError/cycle abort must not inflate
        # the workload signal feeding frequency-aware repack
        with self._lock:
            for vid in vids:
                self.versions[vid].access_count += 1
            self._unflushed_accesses += len(vids)
            if self._unflushed_accesses >= self.access_flush_every:
                self.flush_access_counts()
        return out

    def _checkout_flat(self, vid: int) -> FlatTree:
        """Internal checkout: no access-count bump, same cache/planner path."""
        return self.materializer.checkout(vid)

    def flush_access_counts(self) -> None:
        """Persist access counts accumulated by checkouts since the last
        metadata write (they feed ``repack(use_access_frequencies=True)``
        after a reload)."""
        with self._lock:
            if self._unflushed_accesses:
                self._save_meta()

    def close(self) -> None:
        """Flush pending metadata (access counts).  Safe to call twice."""
        self.flush_access_counts()

    def recreation_cost(self, vid: int) -> float:
        """Modelled Φ along the current storage chain."""
        total = 0.0
        v: Optional[int] = vid
        hops = 0
        while v is not None:
            meta = self.versions[v]
            total += meta.phi
            v = meta.stored_base
            hops += 1
            if hops > len(self.versions):
                raise RuntimeError("storage graph cycle")
        return total

    def storage_bytes(self) -> int:
        return sum(m.stored_bytes for m in self.versions.values())

    # -------------------------------------------------------------- repack
    def build_cost_graph(
        self, *, extra_edges: bool = True
    ) -> Tuple[VersionGraph, _PayloadProvider]:
        """Measure the Δ/Φ matrices over version-graph-adjacent pairs (plus
        pairs within ``delta_hops``) and return (graph, payload provider).

        This is the paper's "revealing entries in the matrix" step: all-pairs
        is infeasible, so we measure around the derivation structure.
        Measured entries are cached in the metadata keyed by the endpoints'
        content fingerprints; unchanged entries are served from the cache
        without touching payloads.  ``last_measured_edges`` records how many
        entries were (re)measured by the call.
        """
        n = len(self.versions)
        g = VersionGraph(n, directed=True)
        provider = _PayloadProvider(self)
        codec = self.objects.codec
        measured = 0

        # legacy metas (pre-fingerprint) get their fingerprint backfilled once
        for vid, meta in self.versions.items():
            if not meta.content_fp:
                meta.content_fp = hashlib.sha256(
                    provider.full_payload(vid)
                ).hexdigest()

        # adjacency of the derivation DAG (undirected, for the hop ball)
        adj: Dict[int, set] = {v: set() for v in self.versions}
        for v, meta in self.versions.items():
            for p in meta.parents:
                adj[v].add(p)
                adj[p].add(v)

        done: set = set()
        for vid, meta in self.versions.items():
            fp = meta.content_fp
            ent = self._edge_cache.get((0, vid))
            if ent is None or ent["dfp"] != fp:
                full_payload = provider.full_payload(vid)
                ent = {
                    "sfp": "",
                    "dfp": fp,
                    "delta": codec.compressed_size(full_payload),
                    "payload_len": len(full_payload),
                    "changed_blocks": 0,
                }
                self._edge_cache[(0, vid)] = ent
                measured += 1
            g.set_materialization(
                vid, ent["delta"],
                self.cost_model.phi_full(ent["delta"], meta.raw_bytes),
            )
            # hop ball
            ball = {vid}
            frontier = {vid}
            hops = self.delta_hops if extra_edges else 1
            for _ in range(hops):
                frontier = {y for x in frontier for y in adj[x]} - ball
                ball |= frontier
            for other in sorted(ball - {vid}):
                if (other, vid) in done:
                    continue
                done.add((other, vid))
                sfp = self.versions[other].content_fp
                ent = self._edge_cache.get((other, vid))
                if ent is None or ent["sfp"] != sfp or ent["dfp"] != fp:
                    payload, stats = provider[(other, vid)]
                    ent = {
                        "sfp": sfp,
                        "dfp": fp,
                        "delta": codec.compressed_size(payload),
                        "payload_len": len(payload),
                        "changed_blocks": stats["changed_blocks"],
                    }
                    self._edge_cache[(other, vid)] = ent
                    measured += 1
                g.set_delta(
                    other, vid, ent["delta"],
                    self.cost_model.phi_delta(
                        ent["delta"], ent["payload_len"], ent["changed_blocks"]
                    ),
                )
        self.last_measured_edges = measured
        if measured:
            self._save_meta()  # persist new measurements for the next call
        return g, provider

    def access_weights(self) -> Dict[int, float]:
        """Normalized access-frequency weights (Laplace-smoothed so an
        unaccessed version still counts) — the workload signal for
        ``repack(use_access_frequencies=True)``."""
        total = sum(m.access_count + 1 for m in self.versions.values())
        return {
            v: (m.access_count + 1) / total for v, m in self.versions.items()
        }

    def repack(
        self,
        spec: Union[OptimizeSpec, str] = "lmg",
        *,
        use_access_frequencies: bool = False,
        **solver_kwargs,
    ) -> Dict[str, Any]:
        """Re-optimize the storage graph against an
        :class:`~repro.core.spec.OptimizeSpec` and rewrite physical storage
        to match.

        ``use_access_frequencies=True`` routes the recorded access counts
        into the spec's ``workload`` field; the spec must name a
        workload-aware grid point (Problems 3 or 5 — LMG), anything else
        raises instead of silently dropping the weights.

        Passing a string solver name plus kwargs is the deprecated legacy
        surface; it is mapped onto the equivalent spec via
        :func:`~repro.core.problems.spec_from_solver`.

        Returns before/after stats, ``gc_freed_bytes`` (orphaned object
        bytes reclaimed by the gc pass — repack never leaves dangling
        objects behind), and an ``optimize`` block recording the problem,
        solver, and backend actually used.
        """
        if isinstance(spec, str):
            warnings.warn(
                "repack(solver: str, **kwargs) is deprecated; pass an "
                "OptimizeSpec (repro.core.OptimizeSpec.problem(...))",
                DeprecationWarning, stacklevel=2,
            )
            spec = spec_from_solver(spec, solver_kwargs)
        elif solver_kwargs:
            raise ValueError(
                f"solver options go inside the OptimizeSpec; got stray "
                f"kwarg(s) {sorted(solver_kwargs)}"
            )
        if not self.versions:
            # nothing to repack: solvers need ≥1 version and the stats below
            # take max() over the version set
            zero = {"storage_bytes": 0, "sum_recreation_s": 0.0,
                    "max_recreation_s": 0.0}
            return {"before": dict(zero), "after": dict(zero),
                    "gc_freed_bytes": 0}
        if use_access_frequencies:
            if not spec.supports_workload():
                raise ValueError(
                    f"use_access_frequencies=True needs a workload-aware "
                    f"spec (Problem 3 or 5 — LMG); got {spec.describe()!r}. "
                    f"The chosen solver would silently ignore the recorded "
                    f"access counts."
                )
            spec = spec.with_workload(self.access_weights())
        with _span("store.repack", spec=spec.describe()) as rsp:
            before = {
                "storage_bytes": self.storage_bytes(),
                "sum_recreation_s": sum(
                    self.recreation_cost(v) for v in self.versions
                ),
                "max_recreation_s": max(
                    self.recreation_cost(v) for v in self.versions
                ),
            }
            with _span("store.measure") as msp:
                g, cache = self.build_cost_graph()
                if msp:
                    msp.set(
                        versions=len(self.versions),
                        measured_edges=self.last_measured_edges,
                    )
            result = optimize(g, spec)
            with _span("store.apply_solution"):
                self._apply_solution(result.solution, cache)
            after = {
                "storage_bytes": self.storage_bytes(),
                "sum_recreation_s": sum(
                    self.recreation_cost(v) for v in self.versions
                ),
                "max_recreation_s": max(
                    self.recreation_cost(v) for v in self.versions
                ),
            }
            with _span("store.gc") as gsp:
                freed = self.gc()
                if gsp:
                    gsp.set(freed_bytes=freed)
            if rsp:
                rsp.set(
                    solver=result.solver,
                    backend=result.backend_used,
                    storage_bytes_before=before["storage_bytes"],
                    storage_bytes_after=after["storage_bytes"],
                )
        self.last_repack = {
            "describe": spec.describe(),
            "problem": result.problem,
            "solver": result.solver,
            "backend": result.backend_used,
            "objective": spec.objective.metric,
            "objective_value": float(result.objective_value),
            "constraints": [
                {"metric": c.metric, "bound": float(c.bound)}
                for c in spec.constraints
            ],
            "timestamp": time.time(),
        }
        self._save_meta()
        if use_access_frequencies:
            # warm the cache with the hottest versions under the *new*
            # storage graph so the first post-repack hit is already served
            # from memory
            hot = sorted(
                self.versions,
                key=lambda v: self.versions[v].access_count,
                reverse=True,
            )[: self.prefetch_hot_k]
            self.materializer.prefetch(hot)
        mon = self.tradeoff_monitor
        if mon is not None:
            mon.on_repack()
        return {
            "before": before,
            "after": after,
            "gc_freed_bytes": freed,
            "optimize": {
                "problem": result.problem,
                "solver": result.solver,
                "backend": result.backend_used,
                "objective_value": result.objective_value,
                "wall_time_s": round(result.wall_time_s, 6),
            },
        }

    def _apply_solution(self, sol: StorageSolution, cache: _PayloadProvider) -> None:
        # phase 1: encode every chosen edge against the *old* storage graph
        # (the provider checkouts must not observe a half-rewritten graph)
        encoded: Dict[int, Tuple[int, bytes, Optional[Dict]]] = {}
        for vid, parent in sol.parent.items():
            payload, stats = cache[(parent, vid)]
            encoded[vid] = (parent, payload, stats)
        # phase 2: rewrite objects and metadata atomically w.r.t. checkouts
        with self._lock:
            for vid, (parent, payload, stats) in encoded.items():
                meta = self.versions[vid]
                key, stored = self.objects.put(payload)
                if parent == 0:
                    meta.stored_base = None
                    meta.phi = self.cost_model.phi_full(stored, meta.raw_bytes)
                else:
                    meta.stored_base = parent
                    meta.phi = self.cost_model.phi_delta(
                        stored, len(payload), stats["changed_blocks"]
                    )
                meta.object_key = key
                meta.stored_bytes = stored
        # storage graph rewritten: chain fingerprints rotated wholesale, so
        # purging beats dropping each stale entry at its next lookup
        self.materializer.cache.purge()

    def gc(self) -> int:
        """Drop objects not referenced by any version; returns bytes freed."""
        live = {m.object_key for m in self.versions.values()}
        freed = 0
        for key in list(self.objects.keys()):
            if key not in live:
                freed += self.objects.stored_size(key)
                self.objects.delete(key)
        return freed

    # ---------------------------------------------------------------- fsck
    def fsck(self, **kwargs: Any):
        """Integrity-check the storage graph; returns an analysis
        :class:`~repro.analysis.findings.Report` (see
        :func:`repro.analysis.fsck.fsck_store` for the checks and kwargs)."""
        from ..analysis.fsck import fsck_store  # local: analysis -> store

        return fsck_store(self, **kwargs)

    # ------------------------------------------------------------ metadata
    def save_refs(self) -> None:
        """Persist the ``refs`` dict (branches/tags/head) with the metadata.

        Called by the :class:`~repro.store.repository.Repository` facade
        after every ref mutation — refs live in the same atomic msgpack file
        as version metas, so a close/reopen round-trips them."""
        self._save_meta()

    def _save_meta(self) -> None:
        with self._lock:
            self._save_meta_locked()

    def _save_meta_locked(self) -> None:
        with _span("store.save_meta"):
            blob = msgpack.packb(
                {
                    "next_vid": self._next_vid,
                    "versions": {
                        str(v): dataclasses.asdict(m) for v, m in self.versions.items()
                    },
                    "edge_cache": {
                        f"{a},{b}": ent for (a, b), ent in self._edge_cache.items()
                    },
                    "refs": {
                        "branches": {
                            name: vid for name, vid in self.refs["branches"].items()
                        },
                        "tags": {name: vid for name, vid in self.refs["tags"].items()},
                        "head": self.refs["head"],
                    },
                    "last_repack": self.last_repack,
                },
                use_bin_type=True,
            )
            fd, tmp = tempfile.mkstemp(dir=str(self.root))
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, self._meta_path)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
            self._unflushed_accesses = 0  # any metadata write persists counts

    def _load_meta(self) -> None:
        obj = msgpack.unpackb(self._meta_path.read_bytes(), raw=False)
        self._next_vid = obj["next_vid"]
        self.versions = {
            int(v): VersionMeta(**m) for v, m in obj["versions"].items()
        }
        self._edge_cache = {}
        for key, ent in obj.get("edge_cache", {}).items():
            a, b = key.split(",")
            self._edge_cache[(int(a), int(b))] = ent
        refs = obj.get("refs") or {}
        self.refs = {
            "branches": {
                str(k): int(v) for k, v in (refs.get("branches") or {}).items()
            },
            "tags": {str(k): int(v) for k, v in (refs.get("tags") or {}).items()},
            "head": str(refs.get("head", "main")),
        }
        self.last_repack = obj.get("last_repack") or None

    # -------------------------------------------------------------- limits
    def log(self) -> List[VersionMeta]:
        return [self.versions[v] for v in sorted(self.versions)]
