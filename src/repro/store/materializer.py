"""Materialization subsystem: planned, cached, batched checkouts.

The paper's whole premise is that recreation cost Φ is paid at *checkout*
time; this module is the layer that actually pays it well.  Every checkout in
the codebase routes through a :class:`Materializer`, which composes three
parts:

* :class:`CheckoutPlanner` — turns one or many requested version ids into an
  explicit :class:`CheckoutPlan` over the storage graph.  A batch is
  topologically ordered (bases before dependents) with shared chain prefixes
  deduplicated, so ``checkout_many([v1..vk])`` decodes each intermediate
  FlatTree exactly once while staying bit-identical to k independent
  checkouts.  The walk is bounded, so corrupted ``stored_base`` metadata
  raises instead of looping.

* :class:`MaterializationCache` — a byte-budgeted LRU of materialized
  FlatTrees keyed by vid, each entry tagged with its vid's *decode-chain*
  fingerprint (:meth:`VersionStore.chain_fingerprint`, a hash over just the
  ``(vid, stored_base, object_key)`` triples along its storage chain).  A
  commit appends triples but rewrites none, so it leaves every warm entry
  valid and interleaved save+serve traffic stays warm; an entry whose chain
  was rewritten is dropped at its next lookup, and repack, which rewrites
  chains wholesale, purges the cache.  A stale tree can never be served —
  an entry is only returned when its tag matches the live chain
  fingerprint.  Cached arrays are marked read-only — a caller mutating a
  checkout result in place would otherwise silently corrupt every future
  checkout of that version.  Cache lookups, inserts and evictions take an
  internal lock so the service tier's reader threads can share one cache.

* :class:`Materializer` — executes plans against the :class:`ObjectStore`
  through the fused, device-resident delta pipeline
  (:func:`~repro.store.delta.apply_delta_chains`), feeding every
  materialized tree (intermediates included — they are exactly the hot
  chain prefixes) through the cache, and exposes hit/decode statistics plus
  ``prefetch`` for repack-time cache warming.  Commits feed the cache too:
  :meth:`Materializer.keep` writes each committed tree through under its
  new vid, so the next commit's parent (and a read of the new tip) is a hit
  rather than a decode from disk.

The cache budget is the ``cache_budget_bytes`` knob on
:class:`~repro.store.version_store.VersionStore` (default 256 MiB; 0 disables
caching but keeps within-batch prefix sharing).
"""

from __future__ import annotations

import collections
import dataclasses
import threading
from typing import (
    TYPE_CHECKING,
    Any,
    Dict,
    Iterable,
    List,
    Optional,
    Sequence,
    Tuple,
)

import jax
import numpy as np

from ..obs.tracer import span as _span
from .delta import (
    BlockedTree,
    FlatTree,
    apply_delta_chains,
    decode_full,
    payload_leaves,
)

if TYPE_CHECKING:  # pragma: no cover - import cycle guard (store owns us)
    from .version_store import VersionStore


def tree_nbytes(flat: FlatTree) -> int:
    """Resident bytes of a materialized tree (what the cache budget counts).

    Charges every leaf in full even though ``apply_delta_chains`` passes
    untouched leaves through by reference between chain-adjacent trees — the
    budget over-, not under-estimates resident memory, so eviction errs
    toward too early.
    """
    return sum(a.nbytes for a in flat.values())


def _freeze(flat: FlatTree) -> FlatTree:
    """Mark every leaf read-only.  Materialized trees alias arrays — across
    the cache, and across batch results via ``apply_delta_chains``'s
    untouched-leaf passthrough — so an in-place write to one checkout
    result would silently corrupt others; numpy turns that into a
    ValueError instead."""
    for arr in flat.values():
        arr.flags.writeable = False
    return flat


def _owned(leaf: Any, arr: np.ndarray) -> np.ndarray:
    """``arr`` (``np.asarray(leaf)``) if no one but the cache can write its
    memory and it is C-contiguous, else a C-contiguous copy.  The caller's
    own NumPy leaf, or a view of it, is copied; so is a view of memory some
    other object owns (a tensor exposing its buffer).  A ``jax.Array``'s
    host value is immutable and is kept as it is."""
    if isinstance(leaf, np.ndarray):
        # bounds overlap: conservative, and O(1) where an exact test is not
        private = not np.may_share_memory(arr, leaf)
    else:
        private = isinstance(leaf, jax.Array) or arr.flags.owndata
    if private and arr.flags.c_contiguous:
        return arr
    return np.array(arr, order="C")


# ------------------------------------------------------------------- planner
@dataclasses.dataclass(frozen=True)
class CheckoutStep:
    """One decode in a plan: full decode (base None) or delta apply."""

    vid: int
    base: Optional[int]
    object_key: str


@dataclasses.dataclass
class CheckoutPlan:
    """Topologically ordered decode schedule for a batch of checkouts.

    ``steps`` lists every decode needed, bases strictly before dependents,
    each vid at most once — shared chain prefixes across the batch appear a
    single time.  ``from_cache`` are vids (requested or chain bases) the
    planner found already materialized; they need no decoding at all.
    """

    requested: List[int]
    steps: List[CheckoutStep]
    from_cache: List[int]

    @property
    def decode_count(self) -> int:
        return len(self.steps)


class CheckoutPlanner:
    """Plans checkouts over the storage graph (a forest: ≤1 base per vid)."""

    def __init__(self, store: "VersionStore") -> None:
        self._store = store

    def plan(
        self, vids: Sequence[int], *, cached: Iterable[int] = ()
    ) -> CheckoutPlan:
        """Plan a batch checkout of ``vids``.

        ``cached`` names vids whose trees are already materialized; chains
        are walked only down to the nearest cached vid (or a full object).
        The walk is bounded by the version count, so a ``stored_base`` cycle
        in corrupted metadata raises ``RuntimeError`` instead of hanging.
        """
        versions = self._store.versions
        cached_set = set(cached)
        needed: Dict[int, CheckoutStep] = {}
        from_cache: List[int] = []
        order: List[int] = []  # needed vids, deepest-base-first per chain

        for vid in vids:
            if vid not in versions:
                raise KeyError(f"unknown version id {vid}")
            chain: List[int] = []
            v: Optional[int] = vid
            while v is not None and v not in needed and v not in cached_set:
                meta = versions[v]
                chain.append(v)
                v = meta.stored_base
                if len(chain) > len(versions):
                    raise RuntimeError("storage graph cycle")
            if v is not None and v in cached_set and v not in from_cache:
                from_cache.append(v)
            for v in reversed(chain):
                meta = versions[v]
                needed[v] = CheckoutStep(
                    vid=v, base=meta.stored_base, object_key=meta.object_key
                )
                order.append(v)

        return CheckoutPlan(
            requested=list(vids),
            steps=[needed[v] for v in order],
            from_cache=from_cache,
        )


# --------------------------------------------------------------------- cache
class MaterializationCache:
    """Byte-budgeted LRU of FlatTrees keyed by vid, fingerprint-validated.

    Every entry is tagged with the decode-chain fingerprint it was
    materialized under; a lookup returns it only when the caller's
    fingerprint matches, and otherwise drops that entry alone
    (``invalidations`` counts them) while the rest of the cache stays warm.
    Commits never rotate the chain fingerprints of existing versions, so
    interleaved commit+serve traffic keeps its hits; ``purge`` (repack)
    drops everything.

    Entries come from checkouts (every materialized tree) and from commits
    (the committed tree, through :meth:`Materializer.keep`).  They are
    evicted least-recently-used once resident bytes exceed
    ``budget_bytes``; a tree larger than the whole budget is simply not
    cached.  All access goes through one internal lock — the service tier's
    reader threads share this cache concurrently.
    """

    def __init__(self, budget_bytes: int) -> None:
        self.budget_bytes = int(budget_bytes)
        self._lock = threading.RLock()
        self._entries: (
            "collections.OrderedDict[int, Tuple[FlatTree, int, str]]"
        ) = collections.OrderedDict()
        self.current_bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidations = 0
        self.purges = 0

    def purge(self) -> None:
        """Drop every entry (repack rewrites chains wholesale; a full purge
        beats lazily discovering n stale tags one lookup at a time)."""
        with self._lock:
            if self._entries:
                self.purges += 1
            self._entries.clear()
            self.current_bytes = 0

    # -- lookup / insert -----------------------------------------------------
    def vids(self) -> List[int]:
        with self._lock:
            return list(self._entries.keys())

    def __contains__(self, vid: int) -> bool:
        with self._lock:
            return vid in self._entries

    def probe(self, vid: int, fp: str) -> bool:
        """Non-counting validity check: is ``vid`` servable under ``fp``?"""
        with self._lock:
            ent = self._entries.get(vid)
            return ent is not None and ent[2] == fp

    def get(
        self, vid: int, fp: str, *, count: bool = True
    ) -> Optional[FlatTree]:
        with self._lock:
            ent = self._entries.get(vid)
            if ent is not None and ent[2] != fp:
                # stale chain tag: this entry alone is dead, drop it
                self._entries.pop(vid)
                self.current_bytes -= ent[1]
                self.invalidations += 1
                ent = None
            if ent is None:
                if count:
                    self.misses += 1
                return None
            self._entries.move_to_end(vid)
            if count:
                self.hits += 1
            return ent[0]

    def put(self, vid: int, tree: FlatTree, fp: str) -> None:
        if self.budget_bytes <= 0:
            return
        nbytes = tree_nbytes(tree)
        if nbytes > self.budget_bytes:
            return
        with self._lock:
            if vid in self._entries:
                self.current_bytes -= self._entries.pop(vid)[1]
            self._entries[vid] = (tree, nbytes, fp)
            self.current_bytes += nbytes
            while self.current_bytes > self.budget_bytes:
                _, (_, old_bytes, _) = self._entries.popitem(last=False)
                self.current_bytes -= old_bytes
                self.evictions += 1

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "invalidations": self.invalidations,
                "purges": self.purges,
                "entries": len(self._entries),
                "current_bytes": self.current_bytes,
                "budget_bytes": self.budget_bytes,
            }


# --------------------------------------------------------------- materializer
@dataclasses.dataclass(eq=False)
class _Segment:
    """A maximal run of delta steps fused into one chain application.

    ``base`` is the tree the run starts from (cached, full-decoded, or an
    earlier segment's terminal); ``steps`` are applied in chain order and
    only the terminal vid's tree is materialized host-side.
    """

    base: int
    steps: List[CheckoutStep]

    @property
    def terminal(self) -> int:
        return self.steps[-1].vid


class Materializer:
    """Executes checkout plans against the object store, through the cache.

    Delta chains run through the device-resident pipeline
    (:func:`repro.store.delta.apply_delta_chains`): intermediate trees stay
    in blocked device form between steps, runs of steps through vids nobody
    else needs collapse into single fused Pallas dispatches, and
    same-shaped leaves across a batch's chains share kernel launches.
    Segment *endpoints* — vids that must exist as host trees — are the
    requested vids, vids with multiple dependents in the plan, and (when
    the cache budget is positive) every vid, so a warm cache holds every
    tree along the chains it served.

    The store's commit path feeds the cache as well (:meth:`keep`): the tree
    just committed is what the next commit on the branch diffs against.
    """

    def __init__(self, store: "VersionStore", *, budget_bytes: int) -> None:
        self._store = store
        self.planner = CheckoutPlanner(store)
        self.cache = MaterializationCache(budget_bytes)
        # decode counters; guarded by _stats_lock — reader-pool threads
        # share one Materializer and the serving benchmark reads these
        self._stats_lock = threading.Lock()
        self.full_decodes = 0
        self.delta_applies = 0
        self.fused_segments = 0
        self.fused_stats: Dict[str, int] = {}

    def probe(self, vid: int) -> bool:
        """Non-counting warm check: would ``checkout(vid)`` be a cache hit?"""
        try:
            return self.cache.probe(vid, self._store.chain_fingerprint(vid))
        except (KeyError, RuntimeError):
            return False  # unknown vid / corrupted chain: not servable

    # -- public API ----------------------------------------------------------
    def checkout(self, vid: int) -> FlatTree:
        """Materialize one version (bit-identical to the raw chain walk)."""
        return self.checkout_many([vid])[0]

    def checkout_many(self, vids: Sequence[int]) -> List[FlatTree]:
        """Materialize a batch, decoding each shared chain prefix once.

        Returns one tree per requested vid, in request order, bit-identical
        to ``[checkout(v) for v in vids]``.  Returned dicts are fresh (safe
        to add/remove keys) but the arrays are shared with the cache and
        read-only.
        """
        with _span("mat.checkout_many", vids=len(vids)) as msp:
            with _span("mat.plan") as psp:
                # the cached vids are *optimistic*: entries are validated
                # lazily at lookup — ``get`` drops a stale tag, and anything
                # that vanished between plan and execute is rebuilt — so a
                # plan never pays a full-cache fingerprint sweep
                plan = self.planner.plan(vids, cached=self.cache.vids())
                if psp:
                    psp.set(
                        steps=plan.decode_count,
                        from_cache=len(plan.from_cache),
                    )
            trees = self._execute(plan)
            out: List[FlatTree] = []
            for vid in plan.requested:
                tree = trees.get(vid)
                if tree is None:
                    tree = self.cache.get(
                        vid, self._store.chain_fingerprint(vid), count=False
                    )
                if tree is None:
                    # the planner saw this vid cached but its entry was
                    # evicted (concurrent checkout sharing the cache) or its
                    # chain tag went stale between plan and execute: rebuild
                    tree = self._materialize_chain(vid, trees)
                out.append(dict(tree))
            if msp:
                planned = {s.vid for s in plan.steps}
                msp.set(
                    decode_steps=plan.decode_count,
                    cache_hits=sum(
                        1 for v in plan.requested if v not in planned
                    ),
                )
            return out

    def prefetch(self, vids: Sequence[int]) -> int:
        """Warm the cache with ``vids`` (hottest first); returns trees cached.

        Used after ``repack(use_access_frequencies=True)``: the top-k most
        accessed versions go straight back into the cache so the first
        post-repack request for a hot version is already warm.
        """
        if self.cache.budget_bytes <= 0:
            return 0
        warmed = 0
        # reversed: LRU evicts oldest inserts first, so load coldest→hottest
        for vid in reversed(list(vids)):
            # validated lookup, not bare containment: a stale-tagged entry
            # (e.g. an out-of-band metadata edit) must be dropped and
            # re-warmed, not treated as present — and dropping it here also
            # keeps the planner from calling it cached below
            fp = self._store.chain_fingerprint(vid)
            if self.cache.get(vid, fp, count=False) is not None:
                continue
            self._execute(self.planner.plan([vid], cached=self.cache.vids()))
            warmed += 1
        return warmed

    def keep(self, vid: int, flat: FlatTree, payload: Any) -> bool:
        """Cache ``flat``, the tree just committed as ``vid``; returns
        whether it was kept.

        Call only once the commit is durable.  The entry is what a checkout
        of ``vid`` would cache: C-contiguous, read-only, keys sorted as
        ``decode_full`` returns them, tagged with ``vid``'s chain
        fingerprint.  A tree the budget cannot hold costs nothing.
        """
        cache = self.cache
        if cache.budget_bytes <= 0 or tree_nbytes(flat) > cache.budget_bytes:
            return False
        leaves = payload_leaves(payload)
        kept = {k: _owned(leaves[k], flat[k]) for k in sorted(flat)}
        cache.put(vid, _freeze(kept), self._store.chain_fingerprint(vid))
        return True

    def stats(self) -> Dict[str, int]:
        with self._stats_lock:
            return {
                **self.cache.stats(),
                "full_decodes": self.full_decodes,
                "delta_applies": self.delta_applies,
                "fused_segments": self.fused_segments,
                "fused_launches": self.fused_stats.get("launches", 0),
            }

    # -- plan execution ------------------------------------------------------
    def _execute(self, plan: CheckoutPlan) -> Dict[int, FlatTree]:
        """Run a plan's decode steps; returns every tree it materialized.

        Within the plan, intermediate trees live in a local dict so prefix
        sharing works even with a zero cache budget; everything decoded is
        also offered to the cache (budget permitting) for future requests.
        """
        trees: Dict[int, FlatTree] = {}
        for vid in plan.from_cache:
            tree = self.cache.get(
                vid, self._store.chain_fingerprint(vid), count=False
            )
            if tree is not None:
                trees[vid] = tree
        self._execute_fused(plan, trees)
        # hit/miss accounting per requested vid — under the cache lock, the
        # counters are shared with every reader-pool thread
        planned = {s.vid for s in plan.steps}
        n_miss = sum(1 for vid in plan.requested if vid in planned)
        with self.cache._lock:
            self.cache.misses += n_miss
            self.cache.hits += len(plan.requested) - n_miss
        return trees

    def _execute_fused(
        self, plan: CheckoutPlan, trees: Dict[int, FlatTree]
    ) -> None:
        """Segment-fused execution through the device-resident delta
        pipeline; adds every tree it materializes to ``trees``, which holds
        the plan's starting trees.

        Delta steps are grouped into :class:`_Segment` runs ending at
        endpoints (requested vids, shared bases, every vid when caching);
        segments whose base tree is ready are batched into one
        :func:`apply_delta_chains` call per wave, so same-shaped leaves
        across independent chains share fused kernel launches.  Blocked
        device forms of segment terminals are memoized locally and fed back
        as ``base_blocked``, so a chain's intermediate trees never pay
        ``to_blocks`` twice.
        """
        objects = self._store.objects
        fp = self._store.chain_fingerprint
        with _span("mat.execute_fused", steps=len(plan.steps)) as esp:
            blocked: Dict[int, BlockedTree] = {}
            n_full = n_delta = n_segments = 0
            wave_stats: Dict[str, int] = {}

            requested = set(plan.requested)
            dependents = collections.Counter(
                s.base for s in plan.steps if s.base is not None
            )
            caching = self.cache.budget_bytes > 0

            def endpoint(vid: int) -> bool:
                return caching or vid in requested or dependents[vid] > 1

            segments: List[_Segment] = []
            open_at: Dict[int, _Segment] = {}
            for step in plan.steps:
                if step.base is None:
                    tree = decode_full(objects.get(step.object_key))
                    n_full += 1
                    trees[step.vid] = _freeze(tree)
                    self.cache.put(step.vid, tree, fp(step.vid))
                    continue
                seg = open_at.pop(step.base, None)
                if seg is None:
                    seg = _Segment(base=step.base, steps=[])
                seg.steps.append(step)
                if endpoint(step.vid):
                    segments.append(seg)
                else:
                    open_at[step.vid] = seg
            # a chain tail is always requested (hence an endpoint), but close
            # any stragglers defensively so no planned step is dropped
            segments.extend(open_at.values())

            pending = segments
            while pending:
                ready = [s for s in pending if s.base in trees]
                if not ready:
                    # base evicted between plan and execute: rebuild it (and
                    # anything under it), then the wave retries
                    self._materialize_chain(pending[0].base, trees)
                    continue
                done = {id(s) for s in ready}
                pending = [s for s in pending if id(s) not in done]
                requests = [
                    (
                        trees[s.base],
                        [objects.get(st.object_key) for st in s.steps],
                        blocked.get(s.base),
                    )
                    for s in ready
                ]
                # wave_stats is plan-local: apply_delta_chains mutates the
                # dict it is given, and self.fused_stats is shared across
                # threads
                results = apply_delta_chains(requests, stats=wave_stats)
                for s, (tree, blk) in zip(ready, results):
                    trees[s.terminal] = _freeze(tree)
                    blocked[s.terminal] = blk
                    self.cache.put(s.terminal, tree, fp(s.terminal))
                    n_delta += len(s.steps)
                    n_segments += 1
            if esp:
                esp.set(
                    full_decodes=n_full,
                    delta_applies=n_delta,
                    fused_segments=n_segments,
                    fused_launches=wave_stats.get("launches", 0),
                )
        with self._stats_lock:
            self.full_decodes += n_full
            self.delta_applies += n_delta
            self.fused_segments += n_segments
            for k, v in wave_stats.items():
                self.fused_stats[k] = self.fused_stats.get(k, 0) + v

    def _materialize_chain(
        self, vid: int, trees: Dict[int, FlatTree]
    ) -> FlatTree:
        """Rebuild ``vid``, missing from cache and plan, into ``trees``.

        Re-planned against the trees already built and run through the same
        executor.  Nothing in the local dict can be evicted, so every base
        of this plan is a full object or in ``trees``: the rebuild never
        falls back again.
        """
        self._execute_fused(self.planner.plan([vid], cached=trees), trees)
        return trees[vid]
