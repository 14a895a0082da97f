"""Multi-vector SpMM path: backends × dtypes × batch widths vs dense A @ X,
plus the B=1 bit-identity regression against the single-vector kernels."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.solvers import block_cg, block_power_iteration, cg
from repro.core.spmv import prepare, spmm, spmv
from repro.configs.spmv_suite import grid_laplacian_2d
from repro.kernels import ops, ref
from repro.kernels.gather import gather, gather_dtype, split_f32
from repro.sparse import CSRMatrix, build_csrk, sellcs_from_csr, tiles_from_csrk


def _irregular_case(rng, m=48, n=48, dtype=np.float32):
    """Skewed row lengths so format="auto" would route to SELL-C-σ."""
    dense = np.zeros((m, n), dtype)
    for i in range(m):
        L = 1 + (i * 7) % 13 + (12 if i % 11 == 0 else 0)
        cols = rng.choice(n, size=min(L, n), replace=False)
        dense[i, cols] = rng.standard_normal(len(cols)).astype(dtype)
    return CSRMatrix.fromdense(dense), dense


def _regular_case(rng, m=64, n=64, density=0.1, dtype=np.float32):
    dense = ((rng.random((m, n)) < density) * rng.standard_normal((m, n))).astype(dtype)
    return CSRMatrix.fromdense(dense), dense


@pytest.mark.parametrize("backend", ["csrk", "sellcs"])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("B", [1, 3, 8])
def test_spmm_backends_dtypes_batches(rng, backend, dtype, B):
    build = _regular_case if backend == "csrk" else _irregular_case
    A, dense = build(rng)
    op = prepare(A, device="tpu_v5e", format=backend)
    X = rng.standard_normal((A.n, B)).astype(np.float32)
    Y = np.asarray(
        op.apply_original(jnp.asarray(X).astype(dtype)), np.float32
    )
    tol = 1e-4 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(Y, dense.astype(np.float32) @ X, rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("backend", ["csrk", "sellcs"])
def test_spmm_b1_bit_identical_to_spmv(rng, backend):
    """[n, 1] input must reproduce the single-vector kernel bit-for-bit —
    the regression gate for the pre-PR B=1 path."""
    build = _regular_case if backend == "csrk" else _irregular_case
    A, _ = build(rng)
    op = prepare(A, device="tpu_v5e", format=backend)
    x = jnp.asarray(rng.standard_normal(A.n), jnp.float32)
    y_vec = np.asarray(op(x))
    y_mat = np.asarray(op(x[:, None]))
    assert y_mat.shape == (A.m, 1)
    assert np.array_equal(y_vec, y_mat[:, 0])


@pytest.mark.parametrize("gather_mode", ["onehot", "take"])
def test_spmm_kernel_gather_modes_match_oracle(rng, gather_mode):
    A, dense = _regular_case(rng, density=0.15)
    k3 = build_csrk(A, srs=4, ssrs=4, k=3)
    tiles = tiles_from_csrk(k3)
    X = rng.standard_normal((A.n, 4)).astype(np.float32)
    Y = ops.spmv_csrk(tiles, jnp.asarray(X), gather_mode=gather_mode, interpret=True)
    Y_ref = ref.spmv_csrk_tiles(tiles, jnp.asarray(X))
    np.testing.assert_allclose(np.asarray(Y), np.asarray(Y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(Y), dense @ X, rtol=2e-3, atol=2e-4)


def test_spmm_sellcs_kernel_matches_oracle(rng):
    A, dense = _irregular_case(rng)
    sell = sellcs_from_csr(A, C=8)
    X = rng.standard_normal((A.n, 5)).astype(np.float32)
    Y_ref = ref.spmv_sellcs(sell, jnp.asarray(X))
    np.testing.assert_allclose(np.asarray(Y_ref), dense @ X, rtol=2e-3, atol=2e-4)
    op = prepare(A, device="tpu_v5e", format="sellcs", gather_mode="take")
    Y = op(jnp.asarray(X))
    np.testing.assert_allclose(np.asarray(Y), dense @ X, rtol=2e-3, atol=2e-4)


def _gather(xT, idx):
    """The kernels' one-hot gather of x[:, idx], run in a Pallas call."""
    xg, parts = split_f32(jnp.asarray(xT))

    def kernel(x_ref, idx_ref, o_ref):
        o_ref[...] = gather(x_ref, idx_ref[...], base=0, chunk=128, parts=parts,
                            dot_dtype=gather_dtype(interpret=True))

    return pl.pallas_call(
        kernel, interpret=True,
        out_shape=jax.ShapeDtypeStruct((xT.shape[0], idx.size), jnp.float32),
    )(xg, jnp.asarray(idx)[None, :])


def test_gather_onehot_batched_matches_looped(rng):
    src = rng.standard_normal((6, 256)).astype(np.float32)   # [B, columns]
    idx = rng.integers(0, 256, size=384).astype(np.int32)
    batched = np.asarray(_gather(src, idx))
    # exact: three bf16 terms of x, each picked out by a one-hot
    np.testing.assert_array_equal(batched, src[:, idx])
    for b in range(src.shape[0]):
        col = np.asarray(_gather(src[b:b + 1], idx))
        np.testing.assert_array_equal(batched[b], col[0])


def test_spmm_out_of_window_remainder_batched(rng):
    """Far off-band entries exercise the batched COO-remainder fold."""
    m = 512  # > 2·window so far entries cannot fit the banded x-window
    dense = np.zeros((m, m), np.float32)
    for i in range(m):
        dense[i, i] = 2.0
        dense[i, (i * 37 + 11) % m] = 1.0
    A = CSRMatrix.fromdense(dense)
    k3 = build_csrk(A, srs=4, ssrs=2, k=3)
    tiles = tiles_from_csrk(k3, window=128)
    assert tiles.remainder_nnz > 0
    X = rng.standard_normal((m, 3)).astype(np.float32)
    Y = ops.spmv_csrk(tiles, jnp.asarray(X), interpret=True)
    np.testing.assert_allclose(np.asarray(Y), dense @ X, rtol=1e-4, atol=1e-5)


def test_matmat_alias_and_cpu_path(rng):
    A, dense = _regular_case(rng)
    op = prepare(A, device="cpu", reorder="natural", format="csrk")
    assert op.tiles is None  # CSR-2 collapse → spmm_csr path
    X = jnp.asarray(rng.standard_normal((A.n, 4)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(op.matmat(X)), dense @ np.asarray(X), rtol=1e-4, atol=1e-4
    )
    with pytest.raises(ValueError):
        op.matmat(X[:, 0])
    np.testing.assert_allclose(
        np.asarray(spmm(A, X)), dense @ np.asarray(X), rtol=1e-4, atol=1e-4
    )


def test_apply_original_matches_seed_scatter(rng):
    """The cached inverse-perm gather must equal the scatter it replaced."""
    A = grid_laplacian_2d(12, 12)
    op = prepare(A, device="tpu_v5e", format="csrk", reorder="bandk")
    x = jnp.asarray(rng.standard_normal(A.n), jnp.float32)
    perm = jnp.asarray(op.perm)
    y_new = op(x[perm])
    y_scatter = np.asarray(jnp.zeros_like(y_new).at[perm].set(y_new))
    np.testing.assert_array_equal(np.asarray(op.apply_original(x)), y_scatter)


def test_block_cg_matches_columnwise_cg(rng):
    A = grid_laplacian_2d(12, 12)
    dense = np.asarray(A.todense())
    X_true = rng.standard_normal((A.m, 4)).astype(np.float32)
    B = jnp.asarray(dense @ X_true)
    res = block_cg(lambda M: spmm(A, M), B, tol=1e-8, maxiter=2000)
    np.testing.assert_allclose(np.asarray(res.X), X_true, rtol=1e-2, atol=1e-2)
    assert res.residual.shape == (4,)
    # agrees with per-column scalar CG
    r0 = cg(lambda v: spmv(A, v), B[:, 0], tol=1e-8, maxiter=2000)
    np.testing.assert_allclose(
        np.asarray(res.X[:, 0]), np.asarray(r0.x), rtol=1e-3, atol=1e-3
    )


def test_block_power_iteration_top_eigs(rng):
    A = grid_laplacian_2d(10, 10)
    dense = np.asarray(A.todense())
    lams = np.asarray(block_power_iteration(lambda M: spmm(A, M), A.m, 3, iters=300))
    true = np.sort(np.linalg.eigvalsh(dense))[::-1][:3]
    np.testing.assert_allclose(lams, true, rtol=5e-2)


@pytest.mark.parametrize("backend", ["csrk", "sellcs"])
def test_spmm_width_fixes_columnwise_bits_at_scale(rng, backend):
    """With ``spmm_width=W`` every launch has one static shape, so
    op(X)[:, i] bit-equals op(x_i) regardless of how columns are grouped.

    This is the serving engine's coalescing contract (requests batched into
    one SpMM must return exactly what a direct call returns).  It must be
    pinned at n ≈ 2-4k: XLA picks contraction schedules per shape, and at
    these sizes un-padded launches at different widths really do differ in
    final-ulp bits (which is why the engine prepares with a fixed width
    rather than relying on natural-width dispatch).
    """
    if backend == "csrk":
        A = grid_laplacian_2d(64, 64)
    else:
        A, _ = _irregular_case(rng, m=1536, n=1536)
    op = prepare(A, device="tpu_v5e", format=backend, spmm_width=8)
    xs = [jnp.asarray(rng.standard_normal(A.n), jnp.float32)
          for _ in range(11)]
    singles = [np.asarray(op(x)) for x in xs]
    # 3 and 8 fit one padded launch; 11 splits into two fixed-width launches
    for B in (3, 8, 11):
        Y = np.asarray(op(jnp.stack(xs[:B], axis=1)))
        for i in range(B):
            np.testing.assert_array_equal(
                Y[:, i], singles[i], err_msg=f"{backend} col {i} of B={B}"
            )
    # a column's bits are independent of its batch neighbours' payloads
    Y1 = np.asarray(op(jnp.stack([xs[0]] + xs[1:8], axis=1)))
    Y2 = np.asarray(op(jnp.stack([xs[0]] + xs[3:10], axis=1)))
    np.testing.assert_array_equal(Y1[:, 0], Y2[:, 0])
