"""The comparison that decides ``correct``.

What the window produced is compared with the configuration's plain
reference (``bench/configs/<config>.py``), which rebuilds each version's tree
from the seed with NumPy and never touches the store.  Every answer is
compared byte for byte, dtype and shape included, so each limit is 0:

* ``commits_lost`` -- commits acknowledged in the window that do not read
  back bit-identical from the store reopened from disk;
* ``requests_failed`` -- requests of the window that raised.

The control puts the reference's answer one step below what the
configuration guarantees (``control`` of the reference module) in the
program's place; it has to come out as not correct.
"""

from __future__ import annotations

from typing import Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

Tree = Dict[str, np.ndarray]
LIMITS = {"commits_lost": 0, "requests_failed": 0}


def bytes_wrong(got: Tree, want: Tree) -> int:
    """Bytes by which ``got`` differs from ``want``: a missing, extra or
    reshaped leaf counts whole; otherwise the differing bytes."""
    wrong = 0
    for key in set(got) | set(want):
        g, w = got.get(key), want.get(key)
        if g is None or w is None or g.dtype != w.dtype or g.shape != w.shape:
            wrong += max(getattr(g, "nbytes", 0), getattr(w, "nbytes", 0), 1)
            continue
        gb = np.ascontiguousarray(g).reshape(-1).view(np.uint8)
        wb = np.ascontiguousarray(w).reshape(-1).view(np.uint8)
        wrong += int(np.count_nonzero(gb != wb))
    return wrong


def expected_trees(ref, cfg: dict, seed: int,
                   wanted: Iterable[int]) -> Iterable[Tuple[int, Tree, Optional[Tree]]]:
    """Yield ``(index, tree, parent_tree)`` for every wanted version index,
    ascending, replaying the saves from the base."""
    wanted = sorted(set(wanted))
    tree: Optional[Tree] = None
    for i in range(wanted[-1] + 1 if wanted else 0):
        parent = tree
        tree = (ref.base_tree(cfg, seed) if parent is None
                else ref.apply(cfg, parent, ref.edit(cfg, seed, i)))
        if i in wanted:
            yield i, tree, parent


def compare(ref, cfg: dict, seed: int, commits: List[Tuple[int, Callable[[], Tree]]],
            failed: int, control: bool = False) -> Dict[str, int]:
    """The compared numbers.  ``commits`` are (version index, read-back
    thunk), read once their expected tree is at hand.  With ``control`` the
    answer compared is the reference's control answer instead of the
    program's."""
    read_back = dict(commits)
    out = {"commits_lost": 0, "requests_failed": failed}
    for i, want, parent in expected_trees(ref, cfg, seed, read_back):
        if control:
            got = ref.control(cfg, want, parent)
        else:
            try:
                got = read_back[i]()
            except Exception:  # unreadable counts as lost
                out["commits_lost"] += 1
                continue
        out["commits_lost"] += bytes_wrong(got, want) > 0
    return out


def checks(numbers: Dict[str, int]) -> Dict[str, Dict[str, int]]:
    return {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}


def passed(numbers: Dict[str, int]) -> bool:
    return all(numbers[k] <= lim for k, lim in LIMITS.items())
