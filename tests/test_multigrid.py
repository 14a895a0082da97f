"""Multigrid-preconditioned CG on HPCG's 27-point stencil, on the CPU.

The hierarchy is 8³ → 4³ → 2³: the fine level forced to CSR-k (four slot
buckets and their combine), the others as ``format="auto"`` routes them
(SELL-C-σ at 4³, CSR-k at 2³, whose rows all hold 8 nonzeros), so both
backends run inside one V-cycle.  Interpret-mode kernels make every
compiled program cost seconds, so each is compiled once per module.

Tolerances: the program computes in f32 and the reference in float64.  A
V-cycle here is about 20 products of up to 27 terms and as many vector
updates, each rounding at 2⁻²⁴ ≈ 6e-8 relative; 1e-5 of ‖z‖∞ leaves that
sum a factor of ten.  Ten PCG iterations compound the same roundings over
ten V-cycles, so the solution is held to 1e-4.
"""
import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from repro.configs.spmv_suite import grid_laplacian_2d, grid_laplacian_3d, hpcg_27pt
from repro.core import multigrid
from repro.core.solvers import cg, jacobi_smoother
from repro.core.spmv import prepare, spmv
from repro.obs import MetricsRegistry, using_registry

N, LEVELS, NU, OMEGA = 8, 3, 2, 0.8
VCYCLE_TOL, PCG_TOL = 1e-5, 1e-4


def _grids():
    return [(N >> l,) * 3 for l in range(LEVELS)]


@pytest.fixture(scope="module")
def mg():
    mats = [hpcg_27pt(*g) for g in _grids()]
    kw = dict(device="tpu_v5e", format="auto", value_dtype="f32")
    fine = prepare(mats[0], **dict(kw, format="csrk"))
    reg = MetricsRegistry()
    with using_registry(reg):
        h = multigrid.hierarchy(mats, [multigrid.injection(g) for g in _grids()[:-1]],
                                nu=NU, omega=OMEGA, prepared=[fine], **kw)
    gauges = {r["name"]: r["value"] for r in reg.records()}
    return h, mats, jax.jit(h.vcycle), gauges


# -- a float64 reference: HPCG's V-cycle with the same smoother ---------------

def _ref_vcycle(mats, r, l=0):
    A = sp.csr_matrix((np.asarray(mats[l].vals, np.float64), np.asarray(mats[l].col_idx),
                       np.asarray(mats[l].row_ptr)), shape=mats[l].shape)
    d = A.diagonal()
    x = OMEGA * r / d
    for _ in range(NU - 1):
        x = x + OMEGA * (r - A @ x) / d
    if l == len(mats) - 1:
        return x
    f2c = np.arange(A.shape[0]).reshape(_grids()[l])[::2, ::2, ::2].ravel()
    x[f2c] += _ref_vcycle(mats, (r - A @ x)[f2c], l + 1)
    for _ in range(NU):
        x = x + OMEGA * (r - A @ x) / d
    return x


def _seeded(k, n=N ** 3):
    return np.random.default_rng(k).standard_normal(n).astype(np.float32)


def test_levels_take_both_backends_and_set_gauges(mg):
    h, mats, _, gauges = mg
    assert [lev.op.backend for lev in h.levels] == ["csrk", "sellcs", "csrk"]
    assert len(h.levels[0].op.tile_buckets.buckets) > 1
    assert gauges["mg.levels"] == LEVELS
    for l, A in enumerate(mats):
        assert gauges[f"mg.rows.l{l}"] == A.m and gauges[f"mg.nnz.l{l}"] == A.nnz
    assert [gauges[f"mg.csrk.l{l}"] for l in range(LEVELS)] == [1.0, 0.0, 1.0]
    assert h.levels[-1].f2c is None
    np.testing.assert_array_equal(np.asarray(h.levels[0].diag), 26.0)


def test_vcycle_matches_float64_reference(mg):
    h, mats, vcycle, _ = mg
    for k in (1, 2):
        r = _seeded(k)
        z = np.asarray(vcycle(jnp.asarray(r)), np.float64)
        z_ref = _ref_vcycle(mats, r.astype(np.float64))
        assert np.max(np.abs(z - z_ref)) <= VCYCLE_TOL * np.max(np.abs(z_ref))


def test_vcycle_is_symmetric_and_positive(mg):
    _, _, vcycle, _ = mg
    us = [_seeded(k) for k in (3, 4, 5)]
    zs = [np.asarray(vcycle(jnp.asarray(u)), np.float64) for u in us]
    for i in range(3):
        assert zs[i] @ us[i] > 0
        for j in range(i + 1, 3):
            a, b = zs[i] @ us[j], us[i] @ zs[j]
            assert abs(a - b) <= VCYCLE_TOL * np.linalg.norm(zs[i]) * np.linalg.norm(us[j])


def test_pcg_matches_float64_reference(mg):
    h, mats, _, _ = mg
    A = sp.csr_matrix((np.asarray(mats[0].vals, np.float64), np.asarray(mats[0].col_idx),
                       np.asarray(mats[0].row_ptr)), shape=mats[0].shape)
    b = A @ _seeded(6).astype(np.float64)
    iters = 10
    res = jax.jit(lambda v: cg(h.levels[0].op.apply_original, v, tol=0.0, maxiter=iters,
                               precond=h.vcycle))(jnp.asarray(b, jnp.float32))
    x, r = np.zeros_like(b), b.copy()
    z = _ref_vcycle(mats, r)
    p, rz = z, r @ z
    for _ in range(iters):
        Ap = A @ p
        alpha = rz / (p @ Ap)
        x, r = x + alpha * p, r - alpha * Ap
        z = _ref_vcycle(mats, r)
        rz, rz_old = r @ z, rz
        p = z + rz / rz_old * p
    assert int(res.iters) == iters
    got = np.asarray(res.x, np.float64)
    assert np.max(np.abs(got - x)) <= PCG_TOL * np.max(np.abs(x))
    assert np.linalg.norm(b - A @ got) < 1e-5 * np.linalg.norm(b)


def _cg_before_precond(matvec, b, tol, maxiter):
    """``cg`` as it was before it took a preconditioner, verbatim."""
    x0 = jnp.zeros_like(b)
    r0 = b - matvec(x0)
    p0 = r0
    rs0 = jnp.vdot(r0, r0)
    tol2 = jnp.asarray(tol, b.dtype) ** 2 * jnp.maximum(jnp.vdot(b, b), 1e-30)
    hist0 = jnp.zeros((maxiter,), jnp.float32)

    def cond(state):
        _, _, _, rs, k, _ = state
        return jnp.logical_and(rs > tol2, k < maxiter)

    def body(state):
        x, r, p, rs, k, hist = state
        Ap = matvec(p)
        alpha = rs / jnp.maximum(jnp.vdot(p, Ap), 1e-30)
        x = x + alpha * p
        r = r - alpha * Ap
        rs_new = jnp.vdot(r, r)
        p = r + (rs_new / jnp.maximum(rs, 1e-30)) * p
        hist = hist.at[k].set(jnp.sqrt(rs_new).astype(jnp.float32))
        return (x, r, p, rs_new, k + 1, hist)

    x, r, _, rs, k, hist = jax.lax.while_loop(cond, body, (x0, r0, p0, rs0, 0, hist0))
    return x, k, jnp.sqrt(rs)


def test_cg_without_preconditioner_is_unchanged_bit_for_bit():
    A = grid_laplacian_2d(20, 20)
    b = jnp.asarray(_seeded(7, A.m))
    mv = lambda v: spmv(A, v)
    new = jax.jit(lambda v: cg(mv, v, tol=1e-6, maxiter=300))(b)
    old = jax.jit(lambda v: _cg_before_precond(mv, v, 1e-6, 300))(b)
    assert int(new.iters) == int(old[1]) > 10
    np.testing.assert_array_equal(np.asarray(new.x).view(np.int32),
                                  np.asarray(old[0]).view(np.int32))
    assert np.asarray(new.residual).tobytes() == np.asarray(old[2]).tobytes()


def test_vcycle_spans_name_every_level(mg):
    h, _, _, _ = mg
    text = str(jax.make_jaxpr(h.vcycle)(jnp.zeros(N ** 3, jnp.float32)).pretty_print(
        source_info=True, name_stack=True))
    for span in ["repro.mg.vcycle", "repro.mg.smooth", "repro.mg.restrict",
                 "repro.mg.prolong"] + [f"repro.mg.level{l}" for l in range(LEVELS)]:
        assert span in text, span


def test_injection_is_hpcgs_f2c():
    f2c = multigrid.injection((4, 6, 2))
    assert f2c.shape == (2 * 3 * 1,)
    fine = np.arange(48).reshape(4, 6, 2)
    coarse = [(i, j, k) for i in range(2) for j in range(3) for k in range(1)]
    assert list(f2c) == [fine[2 * i, 2 * j, 2 * k] for i, j, k in coarse]
    with pytest.raises(ValueError):
        multigrid.injection((4, 5, 2))


def test_hierarchy_refuses_maps_that_do_not_fit():
    mats = [hpcg_27pt(4, 4, 4), hpcg_27pt(2, 2, 2)]
    with pytest.raises(ValueError):
        multigrid.hierarchy(mats, [])
    with pytest.raises(ValueError):
        multigrid.hierarchy(mats, [np.arange(4)])
    with pytest.raises(ValueError):
        multigrid.hierarchy(mats, [multigrid.injection((4, 4, 4))], nu=0)


def test_jacobi_smoother_continues_from_x0(rng):
    A = grid_laplacian_2d(10, 10)
    diag = jnp.asarray(multigrid.diagonal(A))
    b = jnp.asarray(rng.standard_normal(A.m), jnp.float32)
    mv = lambda v: spmv(A, v)
    x3 = jacobi_smoother(mv, diag, b, iters=3, omega=0.8)
    x1 = jacobi_smoother(mv, diag, b, iters=1, omega=0.8)
    np.testing.assert_array_equal(
        np.asarray(jacobi_smoother(mv, diag, b, x1, iters=2, omega=0.8)), np.asarray(x3))


@pytest.mark.parametrize("n", [4, 7])
def test_27_point_stencil_rows_and_count(n):
    A = grid_laplacian_3d(n, n, n, stencil=27)
    lengths = np.diff(np.asarray(A.row_ptr))
    assert sorted(set(lengths.tolist())) == [8, 12, 18, 27]
    assert A.nnz == (3 * n - 2) ** 3
    S = sp.csr_matrix((np.asarray(A.vals), np.asarray(A.col_idx), np.asarray(A.row_ptr)),
                      shape=A.shape)
    assert abs(S - S.T).max() == 0 and S.has_sorted_indices
    np.testing.assert_array_equal(S.diagonal(), 26.0)
    np.testing.assert_array_equal(np.asarray(S.sum(axis=1)).ravel()[lengths == 27], 0.0)
    with pytest.raises(ValueError):
        grid_laplacian_3d(n, n, n, stencil=9)


def test_level0_window_takes_sub_steps_bit_for_bit(monkeypatch):
    """A window too wide for 8 tiles' x blocks a grid step runs the same
    tiles over sub-steps: every tile's result is unchanged, bit for bit."""
    from repro.kernels import spmv_csrk
    from repro.kernels.gather import pick_chunk

    x_tiles = spmv_csrk.x_tiles_per_step
    # ecology1 on the chip host keeps 8; HPCG's 104³ and 52³ windows do not
    assert x_tiles(640, 4864, 3, 256) == 8 and x_tiles(640, 6784, 24, 128) == 8
    assert x_tiles(1536, 91_264, 3, pick_chunk(91_264, 512)) == 1
    assert x_tiles(1536, 26_240, 3, pick_chunk(26_240, 512)) == 4
    A = grid_laplacian_2d(40, 40)
    op = prepare(A, device="tpu_v5e", format="csrk", tile_layout="monolithic")
    assert op.tiles.num_tiles > 8 and len(set(np.asarray(op.tiles.win_block))) > 2
    x = jnp.asarray(_seeded(8, A.m))
    ys = {}
    for X in (8, 2, 1):
        monkeypatch.setattr(spmv_csrk, "x_tiles_per_step", lambda *a, X=X: X)
        spmv_csrk.spmv_csrk_tiles_pallas.clear_cache()
        ys[X] = np.asarray(op(x)).view(np.int32)
    spmv_csrk.spmv_csrk_tiles_pallas.clear_cache()
    np.testing.assert_array_equal(ys[2], ys[8])
    np.testing.assert_array_equal(ys[1], ys[8])
