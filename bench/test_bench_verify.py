"""The comparison's arithmetic and the reference's replay, without a store."""

from __future__ import annotations

import numpy as np
import pytest

from bench import trace, verify

BASE = {"a": np.arange(8, dtype=np.int32), "b": np.ones((2, 3), np.float32)}


def _flip(tree, key):
    arr = np.array(tree[key])
    arr.reshape(-1).view(np.uint8)[0] ^= 1
    return {**tree, key: arr}


@pytest.mark.parametrize("got, want", [
    (BASE, 0),
    (_flip(BASE, "a"), 1),
    ({**BASE, "a": BASE["a"].astype(np.int64)}, 64),
    ({**BASE, "b": BASE["b"].reshape(3, 2)}, 24),
    ({"a": BASE["a"]}, 24),
    ({**BASE, "c": np.zeros(5, np.uint8)}, 5),
])
def test_bench_bytes_wrong_counts(got, want):
    assert verify.bytes_wrong(got, BASE) == want


class _Counting:
    """A reference whose version i holds i in every leaf."""

    @staticmethod
    def base_tree(cfg, seed):
        return {"x": np.zeros(4, np.int64)}

    @staticmethod
    def edit(cfg, seed, index):
        return index

    @staticmethod
    def apply(cfg, tree, change):
        assert int(tree["x"][0]) == change - 1  # applied to its parent
        return {"x": tree["x"] + 1}


def test_bench_expected_trees_replay_from_the_base():
    got = list(verify.expected_trees(_Counting, {}, 0, [5, 2, 2]))
    assert [i for i, _, _ in got] == [2, 5]
    for i, tree, parent in got:
        assert int(tree["x"][0]) == i and int(parent["x"][0]) == i - 1


def test_bench_compare_counts_unreadable_and_wrong_commits():
    def unreadable():
        raise OSError("gone")

    commits = [(1, lambda: {"x": np.ones(4, np.int64)}),
               (2, lambda: {"x": np.ones(4, np.int64)}),
               (3, unreadable)]
    numbers = verify.compare(_Counting, {}, 0, commits, failed=0)
    assert numbers == {"commits_lost": 2, "requests_failed": 0}
    assert not verify.passed(numbers)
    assert verify.passed(verify.compare(_Counting, {}, 0, commits[:1], failed=0))


@pytest.mark.parametrize("least, seconds, share", [
    (819e9, 1.0, 100.0), (819e6, 0.01, 10.0), (0, 1.0, None), (819e9, 0.0, None)])
def test_bench_roofline_share_rules(least, seconds, share):
    got = trace.roofline_share(least, seconds, 819e9)
    assert got == (None if share is None else pytest.approx(share))
