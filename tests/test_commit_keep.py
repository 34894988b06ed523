"""A commit writes its tree through into the materialization cache.

* the next commit's parent is a cache hit: no decode, no delta apply —
  for dense leaves, grown table columns and 64-bit leaves alike;
* the kept tree is what a checkout from the reopened store returns: keys,
  dtypes (bf16 included), shapes and bytes, C-contiguous and read-only;
* the caller's arrays are copied, not aliased, and keep their flags; a
  ``jax.Array``'s host value is kept without a second copy;
* a tree the budget cannot hold is not copied and leaves no entry;
* a commit that fails leaves no entry;
* checkouts of old versions stay correct while commits run.
"""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.store.version_store import VersionStore


def _tree(seed: int):
    rng = np.random.RandomState(seed)
    return {
        "w": rng.randn(64, 128).astype(np.float32),
        "h": rng.randn(32, 64).astype(jnp.bfloat16),
        "step": np.array(seed, dtype=np.int64),
    }


def _next(tree, k: int):
    """``tree`` with one block of ``w`` and the step changed."""
    w = tree["w"].copy()
    w[0, :4] += k
    return {**tree, "w": w, "step": tree["step"] + 1}


def _table(seed: int):
    rng = np.random.RandomState(seed)
    return {
        "field": rng.randint(0, 256, (300, 100)).astype(np.uint8),
        "num_rows": np.array(300, dtype=np.int64),
    }


def _next_table(tree, k: int):
    """``tree`` with ``k`` rows appended and one old row rewritten."""
    field = np.concatenate([tree["field"], np.full((k, 100), k, np.uint8)])
    field[k] = 255 - k
    return {"field": field, "num_rows": np.array(len(field), np.int64)}


def _wide(seed: int):
    rng = np.random.RandomState(seed)
    return {
        "col": rng.randn(3000),
        "ids": rng.randint(2**40, 2**50, size=2000, dtype=np.int64),
    }


def _next_wide(tree, k: int):
    """``tree`` with changes a 32-bit copy could not represent."""
    col, ids = tree["col"].copy(), tree["ids"].copy()
    col[k] *= 1 + 1e-12
    ids[k] += 1
    return {"col": col, "ids": ids}


# (first tree, next tree) per kind of leaf a save carries
TREES = {
    "dense": (_tree, _next),
    "grown": (_table, _next_table),
    "64bit": (_wide, _next_wide),
}


def _cached(store, vid):
    return store.materializer.cache.get(
        vid, store.chain_fingerprint(vid), count=False
    )


def _assert_same(got, want):
    assert set(got) == set(want)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        assert got[k].shape == want[k].shape, k
        assert got[k].tobytes() == want[k].tobytes(), k


def _traced(fn):
    tracer = obs.Tracer(enabled=True)
    old = obs.set_tracer(tracer)
    try:
        out = fn()
    finally:
        obs.set_tracer(old)
    return out, tracer.spans()


@pytest.mark.parametrize("tree", TREES)
def test_next_commit_parent_is_a_hit(tmp_path, tree):
    first, step = TREES[tree]
    store = VersionStore(tmp_path)
    t1 = first(1)
    v1 = store.commit(t1)
    before = store.materializer.stats()
    t2 = step(t1, 1)
    v2, spans = _traced(lambda: store.commit(t2, parents=[v1]))
    after = store.materializer.stats()
    assert after["full_decodes"] == before["full_decodes"]
    assert after["delta_applies"] == before["delta_applies"]
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"]
    assert store.versions[v2].stored_base == v1  # the hit fed a real diff
    (parent,) = [s for s in spans if s.name == "store.parent"]
    (mat,) = [s for s in spans if s.name == "mat.checkout_many"
              and s.parent_id == parent.span_id]
    assert mat.attrs["cache_hits"] == 1 and mat.attrs["decode_steps"] == 0
    (commit,) = [s for s in spans if s.name == "store.commit"]
    (keep,) = [s for s in spans if s.name == "store.keep"]
    assert keep.parent_id == commit.span_id and keep.attrs["kept"] == 1
    # and the chain goes on: the delta-stored tip is the next parent
    t3 = step(t2, 2)
    v3 = store.commit(t3, parents=[v2])
    assert store.versions[v3].stored_base == v2
    assert store.materializer.stats()["full_decodes"] == before["full_decodes"]
    _assert_same(store.checkout(v3), t3)
    # what was kept is what the objects on disk decode to
    _assert_same(VersionStore(tmp_path).checkout(v3), t3)


def test_kept_tree_equals_a_checkout_from_disk(tmp_path):
    base = np.arange(48 * 64, dtype=np.float32).reshape(48, 64)
    t1 = {
        "w": base[:, ::2],                       # non-contiguous
        "wt": base.T,                            # Fortran order
        "h": np.linspace(-3, 3, 40 * 96).reshape(40, 96).astype(jnp.bfloat16),
        "step": np.array(7, dtype=np.int64),     # 0-d
        "j": jnp.arange(300, dtype=jnp.int32),   # a device array
        "n": {"bias": np.ones(5, np.float16), "lr": 0.5},
    }
    store = VersionStore(tmp_path)
    v1 = store.commit(t1)
    t2 = {**t1, "step": np.array(8, dtype=np.int64), "h": t1["h"] + 1}
    v2 = store.commit(t2, parents=[v1])
    kept = {v: _cached(store, v) for v in (v1, v2)}
    reopened = VersionStore(tmp_path)
    for vid in (v1, v2):
        tree, want = kept[vid], reopened.checkout(vid)
        assert tree is not None
        _assert_same(tree, want)
        assert list(tree) == sorted(tree)  # decode_full's key order
        for k, a in tree.items():
            assert a.flags.c_contiguous and not a.flags.writeable, k
        # what the store hands out for the tip is the kept tree itself
        got = store.checkout(vid)
        assert all(got[k] is tree[k] for k in tree)


def test_caller_arrays_are_copied_and_keep_their_flags(tmp_path):
    w = np.zeros((16, 64), np.float32)
    frozen = np.ones(100, np.int32)
    frozen.flags.writeable = False
    j = jnp.arange(64, dtype=jnp.float32)
    store = VersionStore(tmp_path)
    vid = store.commit({"w": w, "frozen": frozen, "j": j})
    assert w.flags.writeable and not frozen.flags.writeable
    w[:] = 5.0  # the caller reuses its buffer for the next step
    got = store.checkout(vid)
    assert not np.shares_memory(got["w"], w)
    assert not np.shares_memory(got["frozen"], frozen)
    assert np.all(got["w"] == 0.0)
    # a jax.Array's host value is immutable: kept as it is, not copied
    assert np.shares_memory(got["j"], np.asarray(j))
    _assert_same(got, VersionStore(tmp_path).checkout(vid))


@pytest.mark.parametrize("budget", [0, 16 << 10])
def test_tree_over_budget_is_not_kept(tmp_path, budget):
    store = VersionStore(tmp_path, cache_budget_bytes=budget)
    t = _tree(3)  # 36 KiB + 8 B, over either budget
    vid, spans = _traced(lambda: store.commit(t))
    (keep,) = [s for s in spans if s.name == "store.keep"]
    assert keep.attrs["kept"] == 0
    assert vid not in store.materializer.cache
    assert store.materializer.cache.current_bytes == 0


def test_failed_commit_leaves_no_entry(tmp_path, monkeypatch):
    store = VersionStore(tmp_path)
    v1 = store.commit(_tree(5))

    def fail():
        raise OSError("disk full")

    monkeypatch.setattr(store, "_save_meta", fail)
    with pytest.raises(OSError):
        store.commit(_next(_tree(5), 1), parents=[v1])
    assert store.materializer.cache.vids() == [v1]


def test_checkouts_of_old_versions_stay_correct_while_commits_run(tmp_path):
    # room for three trees: commits evict what the readers decode
    store = VersionStore(tmp_path, cache_budget_bytes=120 << 10)
    trees = {}
    t = _tree(6)
    vid = store.commit(t)
    trees[vid] = t
    for k in range(3):
        t = _next(t, k + 1)
        vid = store.commit(t, parents=[vid])
        trees[vid] = t
    old = list(trees)
    errors = []
    stop = threading.Event()

    def reader(i):
        try:
            n = 0
            while not stop.is_set() or n < 4:
                v = old[(i + n) % len(old)]
                _assert_same(store.checkout(v), trees[v])
                n += 1
        except Exception as e:  # pragma: no cover - reported below
            errors.append(e)

    threads = [threading.Thread(target=reader, args=(i,)) for i in range(3)]
    for th in threads:
        th.start()
    try:
        for k in range(4):
            t = _next(t, 10 + k)
            vid = store.commit(t, parents=[vid])
            trees[vid] = t
    finally:
        stop.set()
        for th in threads:
            th.join()
    assert not errors, errors
    reopened = VersionStore(tmp_path)
    for v, want in trees.items():
        _assert_same(reopened.checkout(v), want)
