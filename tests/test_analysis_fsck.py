"""Storage-graph fsck tests: a healthy store is clean, and each injected
corruption is reported with the right rule and severity — no more, no less.

Corruption injections (one per test, each asserting the exact finding set):

* ``stored_base`` cycle        → ``fsck.cycle`` ERROR
* deleted object file          → ``fsck.missing-object`` ERROR
* bit-flipped stored payload   → ``fsck.unreadable`` ERROR (codec refuses)
* crafted wrong-content payload→ ``fsck.fingerprint`` ERROR (decodes fine,
  bytes differ — the cache-bypass case)
* delta with a wrong block index → ``fsck.fingerprint`` ERROR (decodes fine)
* dangling branch ref          → ``fsck.ref`` ERROR
* constraint drift post-repack → ``fsck.constraint`` ERROR
* orphaned object              → ``fsck.orphan-object`` WARNING

A clean store's chains are re-decoded hop by hop through
``apply_delta_chain``, the decoder checkouts read through, and each hop must
equal the NumPy reference decoder's, for every kind of leaf a save carries.
"""

import ml_dtypes
import msgpack
import numpy as np
import pytest

from repro.analysis import fsck as fsck_mod
from repro.analysis.cli import build_synthetic_store
from repro.analysis.findings import Severity
from repro.core import OptimizeSpec
from repro.store import Repository, VersionStore
from repro.store.delta import flatten_payload

from reference_decode import apply_delta_ref, checkout_ref


def payload(seed: int, shape=(48, 32)):
    rng = np.random.RandomState(seed)
    return {
        "w": rng.randn(*shape).astype(np.float32),
        "b": rng.randn(shape[1]).astype(np.float32),
    }


@pytest.fixture()
def repo(tmp_path):
    """Four versions forming a delta chain: v1 full, v2..v4 incremental
    edits (small enough that the commit path picks delta storage)."""
    r = Repository(tmp_path / "store")
    tree = payload(0)
    r.commit(tree, message="c0")
    for i in range(1, 4):
        tree = dict(tree)
        w = tree["w"].copy()
        w[i, :4] += 1.0
        tree["w"] = w
        r.commit(tree, message=f"c{i}")
    assert r.store.versions[4].stored_base == 3
    return r


def rule_map(report):
    """{rule: [(subject, severity)]} over the report's findings."""
    out = {}
    for f in report.findings:
        out.setdefault(f.rule, []).append((f.subject, f.severity))
    return out


def _obj_path(store, vid):
    return store.objects._path(store.versions[vid].object_key)


class TestCleanStores:
    def test_fresh_store_is_clean(self, repo):
        assert repo.fsck().findings == []

    def test_synthetic_store_is_clean(self, tmp_path):
        # the CI self-check fixture: commits + branch + tag + repack
        r = build_synthetic_store(tmp_path / "syn")
        report = r.fsck()
        assert report.findings == [], \
            "\n".join(f.render() for f in report.findings)
        # the repack recorded its constraint, and fsck re-validated it
        assert r.store.last_repack["constraints"] == [
            {"metric": "max_recreation", "bound": 10.0}
        ]
        assert report.checked["fsck.constraint"] == 1

    def test_sampling_still_covers_endpoints(self, repo):
        report = repo.fsck(sample=2)
        assert report.findings == []
        assert report.checked["fsck.fingerprint"] == 2


def _edit(a, step):
    """``a`` with its element ``step`` (in C order) changed."""
    out = a.copy()
    out.reshape(-1)[step] += 1
    return out


def _versions(kind, n=4):
    """``n`` successive trees of one leaf kind, beside a large float32 leaf
    that one element of each version changes, so every save is a delta."""
    rng = np.random.default_rng(len(kind))
    big = rng.standard_normal(20_000).astype(np.float32)
    if kind == "float32":
        leaves = {"w": rng.standard_normal((48, 64)).astype(np.float32)}
    elif kind == "bfloat16":
        leaves = {"h": rng.standard_normal((64, 64)).astype(ml_dtypes.bfloat16)}
    elif kind == "float64+int64":
        leaves = {"col": rng.standard_normal(3000),
                  "ids": rng.integers(2**40, 2**50, 2000, dtype=np.int64)}
    elif kind == "grown":
        leaves = {"field": rng.integers(0, 256, (300, 100), dtype=np.uint8),
                  "num_rows": np.array(300, np.int64)}
    else:  # "tombstone" / "rewrite": a small leaf beside the edited one
        leaves = {"w": rng.standard_normal((48, 64)).astype(np.float32),
                  "small": np.arange(10.0, dtype=np.float32).reshape(2, 5)}
    trees = []
    for step in range(n):
        if step:
            leaves = {k: _edit(a, step) if a.ndim and k != "small" else a
                      for k, a in leaves.items()}
            big = big.copy()
            big[step] = -step
        if kind == "grown" and step:
            field = np.concatenate(
                [leaves["field"], np.full((5 * step, 100), step, np.uint8)])
            leaves = {"field": field, "num_rows": np.array(len(field), np.int64)}
        if kind == "tombstone" and step == 2:
            leaves = {k: a for k, a in leaves.items() if k != "small"}
        if kind == "rewrite" and step == 2:
            leaves = {**leaves, "small": np.arange(12, dtype=np.int16)}
        if kind == "rewrite" and step == 3:
            leaves = {**leaves, "small": _edit(leaves["small"], 3)}
        trees.append({"big": big, **leaves})
    return trees


LEAF_KINDS = ["float32", "bfloat16", "float64+int64", "grown", "tombstone",
              "rewrite"]


@pytest.mark.parametrize("kind", LEAF_KINDS)
def test_clean_fsck_decodes_through_the_chain_pipeline(tmp_path, monkeypatch,
                                                       kind):
    store = VersionStore(tmp_path)
    trees = _versions(kind)
    vids = [store.commit(trees[0])]
    for t in trees[1:]:
        vids.append(store.commit(t, parents=[vids[-1]]))
    assert all(store.versions[v].stored_base == p
               for p, v in zip(vids, vids[1:]))
    hops = []
    decode = fsck_mod.apply_delta_chain

    def spy(base, payloads):
        got = decode(base, payloads)
        (payload,) = payloads  # one hop at a time
        want = apply_delta_ref(base, payload)
        assert set(got) == set(want)
        for k in want:
            assert got[k].dtype == want[k].dtype, k
            assert np.array_equal(got[k], want[k]), k
        hops.append(payload)
        return got

    monkeypatch.setattr(fsck_mod, "apply_delta_chain", spy)
    report = store.fsck()
    assert report.findings == [], "\n".join(f.render() for f in report.findings)
    assert report.checked["fsck.fingerprint"] == len(vids)
    assert len(hops) == len(vids) - 1
    for vid, want in zip(vids, trees):
        got = checkout_ref(store, vid)
        assert set(got) == set(want)
        for k in want:
            assert got[k].dtype == want[k].dtype, k
            assert np.array_equal(got[k], want[k]), k


class TestInjectedCorruption:
    def test_stored_base_cycle(self, repo):
        store = repo.store
        a, b = 3, 4
        store.versions[a].stored_base = b
        store.versions[b].stored_base = a
        rm = rule_map(store.fsck())
        assert rm["fsck.cycle"] == [(f"v{a}", Severity.ERROR)]
        # cycle members are excluded from decoding, not double-reported
        assert "fsck.unreadable" not in rm and "fsck.fingerprint" not in rm

    def test_deleted_object_file(self, repo):
        store = repo.store
        _obj_path(store, 2).unlink()
        rm = rule_map(store.fsck())
        assert rm["fsck.missing-object"] == [("v2", Severity.ERROR)]
        # v2's chain descendants can't be decoded, but the one root cause
        # is the only error reported
        assert "fsck.unreadable" not in rm

    def test_bit_flipped_payload_is_unreadable(self, repo):
        store = repo.store
        p = _obj_path(store, 1)
        blob = bytearray(p.read_bytes())
        blob[len(blob) // 2] ^= 0xFF
        p.write_bytes(bytes(blob))
        rm = rule_map(store.fsck())
        subjects = dict(rm["fsck.unreadable"])
        assert subjects["v1"] == Severity.ERROR

    def test_wrong_content_payload_flunks_fingerprint(self, repo):
        # decodes cleanly but bytes differ — only the recomputed content
        # fingerprint can catch this (the materialization cache key cannot:
        # the storage-graph triples are unchanged)
        store = repo.store
        tip = max(store.versions)
        assert store.versions[tip].stored_base is not None
        tampered = dict(store.checkout(tip))
        w = tampered["w"].copy()
        w[0, 0] += 1.0
        tampered["w"] = w
        from repro.store.delta import encode_delta

        base_tree = store.checkout(store.versions[tip].stored_base)
        payload_bytes, _ = encode_delta(base_tree, flatten_payload(tampered))
        _obj_path(store, tip).write_bytes(
            store.objects.codec.compress(payload_bytes)
        )
        rm = rule_map(store.fsck())
        assert rm["fsck.fingerprint"] == [(f"v{tip}", Severity.ERROR)]
        assert "fsck.unreadable" not in rm

    def test_wrong_block_index_flunks_fingerprint(self, repo):
        # the tip's delta still decodes, but writes its changed block at the
        # neighbouring row: only the recomputed content fingerprint sees it
        store = repo.store
        tip = max(store.versions)
        obj = msgpack.unpackb(
            store.objects.get(store.versions[tip].object_key), raw=False)
        entry = obj["sparse"]["w"]
        idx = np.frombuffer(entry["idx"], np.int32)
        assert list(idx) == [0]
        entry["idx"] = (idx + 1).tobytes()
        _obj_path(store, tip).write_bytes(store.objects.codec.compress(
            msgpack.packb(obj, use_bin_type=True)))
        rm = rule_map(store.fsck())
        assert rm == {"fsck.fingerprint": [(f"v{tip}", Severity.ERROR)]}

    def test_dangling_branch_ref(self, repo):
        repo.store.refs["branches"]["ghost"] = 999
        rm = rule_map(repo.fsck())
        assert rm["fsck.ref"] == [("branch:ghost", Severity.ERROR)]

    def test_head_naming_missing_branch(self, repo):
        repo.store.refs["head"] = "nope"
        rm = rule_map(repo.fsck())
        assert rm["fsck.ref"] == [("head:nope", Severity.ERROR)]

    def test_constraint_drift_after_repack(self, repo):
        store = repo.store
        repo.repack(OptimizeSpec.problem(6, theta=10.0))
        m = max(store.recreation_cost(v) for v in store.versions)
        # tighten the recorded bound to just above what the repacked graph
        # achieves — the agreed SLA the commits below will drift past (each
        # delta hop adds at least the cost model's per-object seek latency)
        store.last_repack["constraints"] = [
            {"metric": "max_recreation", "bound": m + 0.004}
        ]
        assert repo.fsck().findings == []  # bound honored right after repack
        tree = dict(repo.checkout())
        for i in range(3):
            tree = dict(tree)
            w = tree["w"].copy()
            w[i] += 1.0
            tree["w"] = w
            repo.commit(tree, message=f"drift {i}")
        assert store.versions[max(store.versions)].stored_base is not None
        rm = rule_map(repo.fsck())
        assert rm["fsck.constraint"] == [
            ("constraint:max_recreation", Severity.ERROR)
        ]

    def test_orphan_object_warns_and_gc_clears(self, repo):
        store = repo.store
        store.objects.put(b"orphaned bytes that no version references")
        rm = rule_map(store.fsck())
        ((subject, sev),) = rm["fsck.orphan-object"]
        assert sev == Severity.WARNING and subject.startswith("object:")
        store.gc()
        assert store.fsck().findings == []

    def test_dangling_parent_and_base(self, repo):
        store = repo.store
        store.versions[4].parents = [99]
        store.versions[3].stored_base = 42
        rm = rule_map(store.fsck())
        assert rm["fsck.dangling-parent"] == [("v4", Severity.ERROR)]
        assert rm["fsck.dangling-base"] == [("v3", Severity.ERROR)]


class TestLastRepackPersistence:
    def test_round_trips_through_metadata(self, tmp_path):
        from repro.store import VersionStore

        root = tmp_path / "s"
        repo = Repository(root)
        for i in range(3):
            repo.commit(payload(i))
        repo.repack(OptimizeSpec.problem(6, theta=10.0))
        lr = repo.store.last_repack
        assert lr["problem"] == 6 and lr["objective"] == "storage"
        repo.close()

        reopened = VersionStore(root)
        assert reopened.last_repack == lr
        # and fsck still validates the recorded constraint after reload
        report = reopened.fsck()
        assert report.findings == []
        assert report.checked["fsck.constraint"] == 1

    def test_unrepacked_store_has_no_record(self, repo):
        assert repo.store.last_repack is None
        assert "fsck.constraint" not in repo.fsck().checked
