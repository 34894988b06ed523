"""Backend-parity property tests: ``backend="jax"`` vs ``backend="numpy"``.

The jitted solver backend (``repro.core.solvers.jax_backend``) runs 32-bit
device selection with host-side f64 cost recomputation: the parent trees must
match the NumPy oracles exactly, and since all reported costs are derived in
f64 from tree + edge arrays, cost equality follows.  Enforced here on the
56-instance random suite of
``test_array_refactor`` (4 synthetic families × 8 seeds + 24 dense random,
directed and undirected) plus corner cases — single version, star graph,
disconnected-but-for-root — and, on a subset, with the Pallas segment
kernels enabled (``pallas=True``, interpret mode on CPU).

The segment-op kernels themselves are unit-tested against NumPy reductions
at the bottom.
"""

import jax
import numpy as np
import pytest

from repro.core import (
    SOLVERS,
    VersionGraph,
    local_move_greedy,
    minimum_storage_tree,
    modified_prim,
    shortest_path_tree,
)
from test_array_refactor import _instances


@pytest.fixture(scope="module")
def instances():
    return _instances()


def _assert_spt_parity(g, **kw):
    a = shortest_path_tree(g)
    b = shortest_path_tree(g, backend="jax", **kw)
    assert a.parent == b.parent
    assert a.recreation_costs() == b.recreation_costs()


def _assert_mst_parity(g, **kw):
    a = minimum_storage_tree(g)
    b = minimum_storage_tree(g, backend="jax", **kw)
    assert a.parent == b.parent
    assert a.storage_cost() == b.storage_cost()


def _assert_lmg_parity(g, mult, **kw):
    budget = minimum_storage_tree(g).storage_cost() * mult
    a = local_move_greedy(g, budget)
    b = local_move_greedy(g, budget, backend="jax", **kw)
    assert a.parent == b.parent
    assert a.storage_cost() == b.storage_cost()
    assert a.sum_recreation() == b.sum_recreation()


def _assert_mp_parity(g, mult, **kw):
    theta = shortest_path_tree(g).max_recreation() * mult
    a = modified_prim(g, theta)
    b = modified_prim(g, theta, backend="jax", **kw)
    assert a.parent == b.parent
    assert a.storage_cost() == b.storage_cost()
    assert a.max_recreation() == b.max_recreation()


class TestBackendParitySuite:
    """Bit-identical trees/costs on the full 56-instance random suite."""

    def test_instance_count(self, instances):
        assert len(instances) >= 50

    def test_spt(self, instances):
        for g in instances:
            _assert_spt_parity(g)

    def test_mst(self, instances):
        # undirected instances exercise the jitted Prim; directed ones the
        # documented Edmonds fallback (identical by construction, still
        # asserted so the dispatch path stays covered)
        for g in instances:
            _assert_mst_parity(g)

    def test_lmg(self, instances):
        for g in instances:
            for mult in (1.05, 1.35):
                _assert_lmg_parity(g, mult)

    def test_mp(self, instances):
        for g in instances:
            for mult in (1.2, 2.5):
                _assert_mp_parity(g, mult)

    def test_solver_registry_accepts_backend(self, instances):
        g = instances[0]
        a = SOLVERS["spt"](g)
        b = SOLVERS["spt"](g, backend="jax")
        assert a.parent == b.parent

    def test_unknown_backend_rejected(self, instances):
        g = instances[0]
        with pytest.raises(ValueError, match="backend"):
            shortest_path_tree(g, backend="torch")
        with pytest.raises(ValueError, match="backend"):
            minimum_storage_tree(g, backend="torch")
        with pytest.raises(ValueError, match="backend"):
            local_move_greedy(g, 1e18, backend="torch")
        with pytest.raises(ValueError, match="backend"):
            modified_prim(g, 1e18, backend="torch")


class TestPallasKernelPath:
    """A subset re-run with the Pallas segment kernels (interpret mode)."""

    def test_parity_with_pallas(self, instances):
        for g in instances[:4]:
            _assert_spt_parity(g, pallas=True)
            _assert_mst_parity(g, pallas=True)
            _assert_lmg_parity(g, 1.2, pallas=True)
            _assert_mp_parity(g, 1.5, pallas=True)


# ------------------------------------------------------------- corner cases
def _star(n, directed):
    """Materializations only — no delta edges at all."""
    g = VersionGraph(n, directed=directed)
    for i in g.versions():
        g.set_materialization(i, 100.0 + i, 50.0 + i)
    return g


def _disconnected_but_for_root(directed):
    """Two delta clusters with no edges between them; the root reaches all."""
    g = VersionGraph(6, directed=directed)
    for i in g.versions():
        g.set_materialization(i, 1000.0 + 10 * i, 900.0 + 10 * i)
    g.set_delta(1, 2, 5.0, 4.0)
    g.set_delta(2, 3, 6.0, 5.0)
    g.set_delta(4, 5, 7.0, 6.0)
    g.set_delta(5, 6, 8.0, 7.0)
    return g


class TestCornerCases:
    @pytest.mark.parametrize("directed", [True, False])
    def test_single_version(self, directed):
        g = VersionGraph(1, directed=directed)
        g.set_materialization(1, 42.0, 17.0)
        _assert_spt_parity(g)
        _assert_mst_parity(g)
        _assert_lmg_parity(g, 1.5)
        _assert_mp_parity(g, 2.0)
        sol = shortest_path_tree(g, backend="jax")
        assert sol.parent == {1: 0}

    @pytest.mark.parametrize("directed", [True, False])
    def test_star_graph(self, directed):
        g = _star(9, directed)
        _assert_spt_parity(g)
        _assert_mst_parity(g)
        # LMG candidate set is empty (SPT == MST == the star)
        _assert_lmg_parity(g, 1.5)
        _assert_mp_parity(g, 1.0)
        assert shortest_path_tree(g, backend="jax").materialized() == list(
            g.versions()
        )

    @pytest.mark.parametrize("directed", [True, False])
    def test_disconnected_but_for_root(self, directed):
        g = _disconnected_but_for_root(directed)
        _assert_spt_parity(g)
        _assert_mst_parity(g)
        _assert_lmg_parity(g, 1.3)
        _assert_mp_parity(g, 1.4)

    def test_near_tie_relaxation_slack_matches(self):
        # a 2-hop path that undercuts the direct edge by ~5e-17 (< the 1e-15
        # relaxation slack): the heap Dijkstra rejects the improvement, and
        # the jitted Bellman-Ford must apply the same EPS guard
        g = VersionGraph(2, directed=True)
        g.set_materialization(1, 10.0, 0.15)
        g.set_materialization(2, 10.0, float(np.nextafter(0.3, 1)))
        g.set_delta(1, 2, 1.0, 0.15)
        _assert_spt_parity(g)
        assert shortest_path_tree(g, backend="jax").parent == {1: 0, 2: 0}

    def test_unreachable_version_raises_like_numpy(self):
        # version 2 has no materialization and no in-edges at all
        g = VersionGraph(2, directed=True)
        g.set_materialization(1, 10.0, 10.0)
        with pytest.raises(ValueError, match="unreachable"):
            shortest_path_tree(g)
        with pytest.raises(ValueError, match="unreachable"):
            shortest_path_tree(g, backend="jax")

    def test_degree_skew_guard(self):
        # a hub vertex whose degree would blow up the dense padded layout
        # must produce a clear error, not an OOM (numpy handles it in CSR)
        from repro.core.solvers import jax_backend

        n = 8192
        g = VersionGraph(n, directed=True)
        ids = np.arange(1, n + 1, dtype=np.int64)
        ones = np.ones(n, dtype=np.float64)
        g.add_edges_bulk(np.zeros(n, dtype=np.int64), ids, 100 * ones, ones)
        hub_dst = ids[1:]  # vertex 1 -> everyone else
        g.add_edges_bulk(
            np.full(n - 1, 1, dtype=np.int64), hub_dst,
            ones[1:], ones[1:],
        )
        assert 16384 * hub_dst.shape[0] > jax_backend.MAX_PADDED_CELLS
        with pytest.raises(ValueError, match="degree skew"):
            modified_prim(g, 1e9, backend="jax")
        # the numpy backend still solves the same instance
        modified_prim(g, 1e9).validate()


# --------------------------------------------------------- segment-op kernels
class TestSegmentOps:
    """Unit tests run under enable_x64 to check the kernels are
    dtype-polymorphic; the production solver path feeds them f32/i32."""

    def _rows(self, seed, shape=(37, 19)):
        rng = np.random.RandomState(seed)
        x = rng.uniform(-100, 100, size=shape)
        x[rng.rand(*shape) < 0.15] = np.inf  # padding-like entries
        return x

    @pytest.mark.parametrize("use_pallas", [True, False])
    def test_row_min_matches_numpy(self, use_pallas):
        from repro.kernels.segment_ops import segment_min_rows

        with jax.enable_x64(True):
            for seed in range(3):
                x = self._rows(seed)
                got = np.asarray(segment_min_rows(x, use_pallas=use_pallas))
                np.testing.assert_array_equal(got, x.min(axis=1))

    @pytest.mark.parametrize("use_pallas", [True, False])
    def test_row_argmin_first_occurrence(self, use_pallas):
        from repro.kernels.segment_ops import segment_argmin_rows

        with jax.enable_x64(True):
            x = self._rows(7)
            x[:, 3] = x[:, 11] = -500.0  # forced ties within every row
            got = np.asarray(segment_argmin_rows(x, use_pallas=use_pallas))
            np.testing.assert_array_equal(got, x.argmin(axis=1))

    @pytest.mark.parametrize("use_pallas", [True, False])
    def test_min_argmin_1d(self, use_pallas):
        import jax.numpy as jnp
        from repro.kernels.segment_ops import min_argmin_1d

        with jax.enable_x64(True):
            rng = np.random.RandomState(0)
            for n in (1, 5, 128, 301):
                x = rng.uniform(-10, 10, size=n)
                if n > 200:
                    x[57] = x[260] = x.min() - 5.0  # cross-tile tie
                m, i = min_argmin_1d(jnp.asarray(x), use_pallas=use_pallas)
                assert int(i) == int(np.argmin(x))
                assert float(m) == x.min()

    @pytest.mark.parametrize("use_pallas", [True, False])
    def test_min_argmin_all_inf(self, use_pallas):
        import jax.numpy as jnp
        from repro.kernels.segment_ops import min_argmin_1d

        with jax.enable_x64(True):
            x = jnp.full((40,), jnp.inf)
            m, i = min_argmin_1d(x, use_pallas=use_pallas)
            assert int(i) == 0 and not np.isfinite(float(m))
