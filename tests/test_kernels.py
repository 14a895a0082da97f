"""Per-kernel allclose sweeps: Pallas (interpret) vs pure-jnp oracles,
across shapes, dtypes, tunings and gather modes."""
import dataclasses

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.formats import CSRMatrix, build_csrk, tiles_from_csrk, ell_from_csr
from repro.core.spmv import prepare
from repro.kernels import ops, ref
from repro.configs.spmv_suite import grid_laplacian_2d, road_graph


def _case(rng, m, n, density, dtype=np.float32):
    dense = ((rng.random((m, n)) < density) * rng.standard_normal((m, n))).astype(dtype)
    A = CSRMatrix.fromdense(dense)
    x = rng.standard_normal(n).astype(dtype)
    return A, dense, x


@pytest.mark.parametrize("m,n,density", [
    (32, 32, 0.1), (64, 48, 0.05), (128, 128, 0.02),
    (96, 96, 0.3), (8, 256, 0.1), (256, 8, 0.5),
])
@pytest.mark.parametrize("srs,ssrs", [(4, 2), (8, 4), (2, 8)])
def test_csrk_kernel_shape_sweep(rng, m, n, density, srs, ssrs):
    A, dense, x = _case(rng, m, n, density)
    k3 = build_csrk(A, srs=srs, ssrs=ssrs, k=3)
    tiles = tiles_from_csrk(k3)
    y_kernel = ops.spmv_csrk(tiles, jnp.asarray(x), interpret=True)
    y_ref = ref.spmv_csrk_tiles(tiles, jnp.asarray(x))
    y_csr = ref.spmv_csr(A, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_kernel), dense @ x, rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_csr), dense @ x, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_csrk_kernel_dtypes(rng, dtype):
    A, dense, x = _case(rng, 64, 64, 0.1, np.float32)
    k3 = build_csrk(A, srs=8, ssrs=2, k=3)
    tiles = tiles_from_csrk(k3)
    import dataclasses
    tiles_d = dataclasses.replace(
        tiles, vals=tiles.vals.astype(dtype), rem_val=tiles.rem_val.astype(dtype)
    )
    y = ops.spmv_csrk(tiles_d, jnp.asarray(x).astype(dtype), interpret=True)
    tol = 1e-4 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(y, np.float32), dense @ x, rtol=tol, atol=tol * 10
    )


@pytest.mark.parametrize("gather_mode", ["onehot", "take"])
def test_csrk_gather_modes(rng, gather_mode):
    A, dense, x = _case(rng, 64, 64, 0.15)
    k3 = build_csrk(A, srs=4, ssrs=4, k=3)
    tiles = tiles_from_csrk(k3)
    y = ops.spmv_csrk(tiles, jnp.asarray(x), gather_mode=gather_mode, interpret=True)
    np.testing.assert_allclose(np.asarray(y), dense @ x, rtol=2e-3, atol=2e-4)


def test_csrk_banded_suite_matrix(rng):
    """Band-k + tuner + kernel end-to-end on a real suite matrix."""
    A = grid_laplacian_2d(32, 32)
    x = jnp.asarray(rng.standard_normal(A.m), jnp.float32)
    op = prepare(A, device="tpu_v5e", reorder="bandk")
    y = op.apply_original(x)
    y_ref = ref.spmv_csr(A, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-4, atol=1e-4)
    # banding must keep the remainder empty (the x-window claim)
    assert op.tiles.remainder_nnz == 0


def test_csrk_out_of_window_remainder(rng):
    """Adversarial structure: far off-band entries fall into the COO
    remainder and the result is still exact."""
    m = 64
    dense = np.zeros((m, m), np.float32)
    for i in range(m):
        dense[i, i] = 2.0
        dense[i, (i * 37 + 11) % m] = 1.0   # scattered far entries
    A = CSRMatrix.fromdense(dense)
    k3 = build_csrk(A, srs=4, ssrs=2, k=3)
    tiles = tiles_from_csrk(k3, window=128)
    x = rng.standard_normal(m).astype(np.float32)
    y = ops.spmv_csrk(tiles, jnp.asarray(x), interpret=True)
    np.testing.assert_allclose(np.asarray(y), dense @ x, rtol=1e-4, atol=1e-5)


def test_ell_kernel(rng):
    A, dense, x = _case(rng, 48, 48, 0.1)
    ell = ell_from_csr(A)
    y = ops.spmv_ell(ell, jnp.asarray(x), row_tile=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y), dense @ x, rtol=2e-3, atol=2e-4)


def test_listing1_structural_oracle(rng):
    """The paper's Listing 1 loop nest (fori_loop transcription) agrees."""
    A, dense, x = _case(rng, 40, 40, 0.2)
    k3 = build_csrk(A, srs=4, ssrs=2, k=3)
    y = ref.spmv_csrk_loops(k3, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), dense @ x, rtol=2e-3, atol=2e-4)


def test_spmv_linearity(rng):
    """Property: SpMV is linear — kernel(a·x + b·z) = a·kernel(x) + b·kernel(z)."""
    A, dense, x = _case(rng, 64, 64, 0.1)
    z = rng.standard_normal(64).astype(np.float32)
    k3 = build_csrk(A, srs=8, ssrs=2, k=3)
    tiles = tiles_from_csrk(k3)
    f = lambda v: np.asarray(ops.spmv_csrk(tiles, jnp.asarray(v), interpret=True))
    lhs = f(2.0 * x - 3.0 * z)
    rhs = 2.0 * f(x) - 3.0 * f(z)
    np.testing.assert_allclose(lhs, rhs, rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# the CSR-k chunk table: a tile's one-hot gather visits only its chunks
# ---------------------------------------------------------------------------

def _edge_case():
    """Three 8-row tiles over a 512-column window (2·W = 1024 columns).

    Tile 0 reads its window's first and last columns and both sides of every
    128-column edge, the block edge 511 | 512 among them; tile 1 starts in
    window block 1 and stores an explicit zero at column 1400; tile 2 holds
    no entry at all, so it is a tile with nothing to read.
    """
    entries = {
        (0, 0): 1.0, (0, 127): 2.0, (1, 128): -1.5, (2, 255): 0.5,
        (3, 256): 3.0, (4, 511): -2.0, (5, 512): 1.25, (6, 639): 4.0,
        (7, 1023): -0.75,
        (8, 700): 1.0, (9, 1023): 2.5, (10, 1024): -3.0, (11, 1100): 0.25,
        (12, 1400): 0.0, (15, 1535): 1.5,
    }
    rows, cols = zip(*entries)
    order = np.lexsort((cols, rows))
    r, c = np.asarray(rows)[order], np.asarray(cols)[order]
    v = np.asarray(list(entries.values()), np.float32)[order]
    m, n = 24, 2048
    A = CSRMatrix(jnp.asarray(np.searchsorted(r, np.arange(m + 1)), jnp.int32),
                  jnp.asarray(c, jnp.int32), jnp.asarray(v), (m, n))
    dense = np.zeros((m, n), np.float32)
    dense[r, c] = v
    return tiles_from_csrk(build_csrk(A, srs=4, ssrs=2, k=3), window=512), dense


def _all_listed(tiles):
    """The same view with every 128-block of every window listed: every
    tile sweeps every chunk, as the kernel did before it read a table."""
    T, nb = tiles.num_tiles, 2 * tiles.window // 128
    table = np.concatenate([np.full((T, 1), nb), np.tile(np.arange(nb), (T, 1))], 1)
    return dataclasses.replace(tiles, col_blocks=jnp.asarray(table, jnp.int32))


def test_chunk_table_lists_edges_zeros_and_no_padding():
    tiles, _ = _edge_case()
    assert tiles.num_tiles == 3 and tiles.window == 512
    np.testing.assert_array_equal(np.asarray(tiles.win_block), [0, 1, 0])
    cb = np.asarray(tiles.col_blocks)
    listed = [list(row[1:1 + row[0]]) for row in cb]
    # tile 0: columns 0..1023 → window blocks 0..7, both sides of each edge
    assert listed[0] == [0, 1, 2, 3, 4, 7]
    # tile 1: columns 700, 1023 | 1024, 1100, the stored zero at 1400, 1535
    # (window start 512) → blocks 1, 3 | 4, 4, 6, 7
    assert listed[1] == [1, 3, 4, 6, 7]
    # the empty tile lists nothing; its padding slots sit at column 0
    assert listed[2] == [] and np.asarray(tiles.local_col)[2].max() == 0
    # chunk 256 (two blocks) and 512 (four): distinct chunks, not blocks
    np.testing.assert_array_equal(tiles.chunks_visited(128), [6, 5, 0])
    np.testing.assert_array_equal(tiles.chunks_visited(256), [4, 4, 0])
    np.testing.assert_array_equal(tiles.chunks_visited(512), [2, 2, 0])


@pytest.mark.parametrize("chunk", [128, 256, 512])
def test_chunk_table_kernel_bit_for_bit_at_edges(rng, chunk):
    tiles, dense = _edge_case()
    x = jnp.asarray(rng.standard_normal(dense.shape[1]), jnp.float32)
    X = jnp.asarray(rng.standard_normal((dense.shape[1], 2)), jnp.float32)
    for v in (x, X):
        run = lambda t: np.asarray(
            ops.spmv_csrk(t, v, gather_chunk=chunk, interpret=True)).view(np.int32)
        y = run(tiles)
        np.testing.assert_array_equal(y, run(_all_listed(tiles)))
        np.testing.assert_allclose(y.view(np.float32), dense @ np.asarray(v),
                                   rtol=1e-6, atol=1e-6)
        # the tile with nothing to read gives exact zeros
        assert not y[16:].any()


def test_chunk_table_stored_zero_meets_x_and_padding_does_not():
    """An explicitly stored zero keeps ``0·x``: an inf at its column makes
    its row NaN, as in CSR.  A padding slot reads nothing: an inf at the
    window column padding points at leaves a tile that lists no block
    there finite, where the full sweep would spread it over the tile."""
    tiles, _ = _edge_case()
    x = np.ones(2048, np.float32)
    x[1400] = np.inf                       # tile 1's stored zero
    y = np.asarray(ops.spmv_csrk(tiles, jnp.asarray(x), gather_chunk=128,
                                 interpret=True))
    assert np.isnan(y[12]) and np.isfinite(y[:8]).all()
    x = np.ones(2048, np.float32)
    x[512] = np.inf                        # column 0 of tile 1's window
    y = np.asarray(ops.spmv_csrk(tiles, jnp.asarray(x), gather_chunk=128,
                                 interpret=True))
    assert np.isfinite(y[8:]).all()
    assert np.isnan(y[5])                  # tile 0 stores column 512
    y_full = np.asarray(ops.spmv_csrk(_all_listed(tiles), jnp.asarray(x),
                                      gather_chunk=128, interpret=True))
    assert np.isnan(y_full[8:16]).all()


@pytest.mark.parametrize("fmt", ["sellcs", "segsum"])
def test_whole_x_kernels_exact_on_integers(rng, fmt):
    """SELL-C-σ and segsum keep sweeping all of x: on small integers every
    product and sum is exact, so their results equal A·x bit for bit."""
    m = n = 384
    dense = np.zeros((m, n), np.float32)
    for i in range(m):
        deg = 1 + (i * 7) % 9 + (40 if i % 53 == 0 else 0)
        cols = rng.choice(n, size=deg, replace=False)
        dense[i, cols] = rng.integers(-4, 5, size=deg)
    x = rng.integers(-8, 9, size=n).astype(np.float32)
    op = prepare(CSRMatrix.fromdense(dense), device="tpu_v5e", format=fmt)
    assert op.backend == fmt
    np.testing.assert_array_equal(np.asarray(op(jnp.asarray(x))), dense @ x)
