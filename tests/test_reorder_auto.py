"""``prepare(reorder="auto")``: keep the input order where it already bounds
the band, Band-k elsewhere; and ``apply_original`` with an identity ``perm``.

The decision compares the input's bandwidth with the bound a Cuthill–McKee
order reaches over breadth-first level sets, ``max_k(|L_k| + |L_{k+1}|) − 1``
from a pseudo-peripheral node.  Its vectorised BFS is checked against the
per-node loops Band-k itself runs (``ordering._bfs_levels``,
``_pseudo_peripheral``, ``_component_of``).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.spmv_suite import grid_laplacian_2d, hpcg_27pt, road_graph
from repro.core import ordering
from repro.core.formats import CSRMatrix
from repro.core.spmv import prepare
from repro.obs import MetricsRegistry, using_registry

U = 2.0 ** -24


def _shuffled(A, seed):
    return A.symmetric_permute(np.random.default_rng(seed).permutation(A.m))


def _block_diag(*mats):
    """The matrices on one diagonal, with no coupling: one component each."""
    rps, cis, vls, off, nnz = [np.zeros(1, np.int64)], [], [], 0, 0
    for A in mats:
        rps.append(np.asarray(A.row_ptr)[1:].astype(np.int64) + nnz)
        cis.append(np.asarray(A.col_idx) + off)
        vls.append(np.asarray(A.vals))
        off, nnz = off + A.m, nnz + A.nnz
    return CSRMatrix(jnp.asarray(np.concatenate(rps), jnp.int32),
                     jnp.asarray(np.concatenate(cis), jnp.int32),
                     jnp.asarray(np.concatenate(vls)), (off, off))


def _isolated(n):
    """n rows holding only their diagonal."""
    return CSRMatrix(jnp.arange(n + 1, dtype=jnp.int32), jnp.arange(n, dtype=jnp.int32),
                     jnp.ones(n, jnp.float32), (n, n))


def _graph_bound(g):
    return ordering.level_bandwidth([(g.adj_ptr, g.adj_idx)], np.diff(g.adj_ptr))


def _reference_level_bandwidth(g):
    """The same bound from Band-k's own loops: every component, its
    pseudo-peripheral node, that node's BFS levels."""
    visited, best = np.zeros(g.n, bool), 0
    for v in range(g.n):
        if visited[v]:
            continue
        component = ordering._component_of(g, v, visited)
        visited[component] = True
        start = ordering._pseudo_peripheral(g, component)
        sizes = np.bincount(ordering._bfs_levels(g, start)[component])
        if len(sizes) > 1:
            best = max(best, int((sizes[:-1] + sizes[1:]).max()) - 1)
    return best


def _prepare_recorded(A, **kw):
    with using_registry(MetricsRegistry()) as reg:
        op = prepare(A, device="tpu_v5e", format="csrk", **kw)
        gauges = {k: reg.get("prepare", f"reorder.{k}")
                  for k in ("kept_input_order", "band_input", "band_levels")}
    return op, gauges


# --- the decision -----------------------------------------------------------

GRAPHS = {
    "grid": lambda: grid_laplacian_2d(9, 7),
    "road": lambda: road_graph(300, seed=5),
    "disconnected": lambda: _block_diag(grid_laplacian_2d(5, 4), _isolated(3),
                                        road_graph(60, seed=2), grid_laplacian_2d(2, 9)),
}


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_level_sets_match_the_reference_bfs(name):
    g = ordering.graph_from_csr(GRAPHS[name]())
    for start in range(0, g.n, 7):
        levels = np.full(g.n, -1, np.int64)
        sets = ordering.level_sets([(g.adj_ptr, g.adj_idx)], start, levels)
        want = ordering._bfs_levels(g, start)
        np.testing.assert_array_equal(levels, want)
        for d, s in enumerate(sets):
            np.testing.assert_array_equal(s, np.flatnonzero(want == d))


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_level_bandwidth_matches_band_k_own_search(name):
    g = ordering.graph_from_csr(GRAPHS[name]())
    assert _graph_bound(g) == _reference_level_bandwidth(g)


@pytest.mark.parametrize("k", [2, 5, 16, 33])
def test_level_bandwidth_of_a_square_grid_is_2k_minus_2(k):
    assert _graph_bound(ordering.graph_from_csr(grid_laplacian_2d(k, k))) == 2 * k - 2


def test_level_bandwidth_without_edges_is_zero():
    assert _graph_bound(ordering.graph_from_csr(_isolated(5))) == 0
    assert ordering.keeps_input_order(_isolated(5)) == (True, 0, 0)


# --- prepare(reorder="auto") ------------------------------------------------

#: (matrix, its bandwidth, the level bound) in the order they arrive
KEPT = {
    "grid5pt": (lambda: grid_laplacian_2d(24, 24), 24, 46),
    "grid27pt": (lambda: hpcg_27pt(6, 6, 6), 43, 6 ** 3 - 4 ** 3 - 1),
    "rect_short_rows": (lambda: grid_laplacian_2d(200, 8), 8, 15),
}

REORDERED = {
    "grid5pt_shuffled": lambda: _shuffled(grid_laplacian_2d(24, 24), 11),
    "grid27pt_shuffled": lambda: _shuffled(hpcg_27pt(6, 6, 6), 12),
    "rect_stride_rows": lambda: grid_laplacian_2d(8, 200),
}


@pytest.mark.parametrize("name", sorted(KEPT))
def test_auto_keeps_an_order_that_bounds_the_band(name):
    build, band_input, band_levels = KEPT[name]
    A = build()
    op, gauges = _prepare_recorded(A)
    assert gauges == {"kept_input_order": 1.0, "band_input": band_input,
                      "band_levels": band_levels}
    np.testing.assert_array_equal(op.perm, np.arange(A.m))
    assert op._identity_perm and op._perm_arrays == ()

    # the same f32 products as Band-k's operator, summed in another order
    ref = prepare(A, device="tpu_v5e", format="csrk", reorder="bandk")
    x = np.random.default_rng(3).standard_normal(A.n).astype(np.float32)
    y, y_ref = (np.asarray(o.apply_original(jnp.asarray(x)), np.float64)
                for o in (op, ref))
    rp, ci = np.asarray(A.row_ptr), np.asarray(A.col_idx)
    rows = np.repeat(np.arange(A.m), np.diff(rp))
    mag = np.zeros(A.m)
    np.add.at(mag, rows, np.abs(np.asarray(A.vals, np.float64) * x[ci]))
    bound = 2 * (np.diff(rp) + 32) * U * mag
    assert np.all(np.abs(y - y_ref) <= bound)


@pytest.mark.parametrize("name", sorted(REORDERED))
def test_auto_takes_band_k_where_the_order_is_wider(name):
    A = REORDERED[name]()
    op, gauges = _prepare_recorded(A)
    assert gauges["kept_input_order"] == 0.0
    assert gauges["band_input"] == ordering.bandwidth(A) > gauges["band_levels"]
    np.testing.assert_array_equal(op.perm, ordering.bandk(A, k=3))
    forced = prepare(A, device="tpu_v5e", format="csrk", reorder="bandk")
    np.testing.assert_array_equal(op.perm, forced.perm)
    assert op.tiles.window == forced.tiles.window
    assert not op._identity_perm


@pytest.mark.parametrize("reorder", ["bandk", "rcm", "natural"])
def test_explicit_orders_skip_the_decision(reorder):
    A = grid_laplacian_2d(12, 12)
    op, gauges = _prepare_recorded(A, reorder=reorder)
    assert all(v is None for v in gauges.values()), gauges
    want = {"bandk": lambda: ordering.bandk(A, k=3), "rcm": lambda: ordering.rcm(A),
            "natural": lambda: np.arange(A.m)}[reorder]()
    np.testing.assert_array_equal(op.perm, want)


def test_auto_stats_report_the_order_used():
    A = grid_laplacian_2d(16, 16)
    assert prepare(A, device="tpu_v5e").stats.bandwidth == 16
    B = _shuffled(A, 5)
    op = prepare(B, device="tpu_v5e")
    assert op.stats.bandwidth == ordering.bandwidth(op.csr) < ordering.bandwidth(B)


# --- apply_original ---------------------------------------------------------

IDENTITY_OPS = {
    "csrk_natural": lambda A: prepare(A, device="tpu_v5e", format="csrk"),
    "sellcs": lambda A: prepare(A, device="tpu_v5e", format="sellcs"),
}


@pytest.mark.parametrize("name", sorted(IDENTITY_OPS))
def test_identity_perm_apply_original_is_the_call(name):
    A = grid_laplacian_2d(20, 20)
    op = IDENTITY_OPS[name](A)
    assert op._identity_perm and op._perm_arrays == ()
    rng = np.random.default_rng(4)
    for shape in ((A.n,), (A.n, 3)):
        x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        assert bool(jnp.all(op.apply_original(x) == op(x)))
        with using_registry(MetricsRegistry()):         # spans on
            assert str(jax.make_jaxpr(op.apply_original)(x)) == \
                str(jax.make_jaxpr(op.__call__)(x))


def test_band_k_apply_original_still_permutes():
    A = _shuffled(grid_laplacian_2d(20, 20), 9)
    op = prepare(A, device="tpu_v5e", format="csrk", reorder="bandk")
    assert not op._identity_perm
    x = jnp.asarray(np.random.default_rng(6).standard_normal(A.n), jnp.float32)
    perm = jnp.asarray(op.perm)
    want = op(x[perm])[jnp.asarray(np.argsort(op.perm))]
    assert bool(jnp.all(op.apply_original(x) == want))
