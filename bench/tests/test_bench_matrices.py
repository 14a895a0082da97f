"""bench/matrices reproduces the library's suite analogue, and seeds its values."""
import json
import os

import numpy as np
import pytest
import scipy.sparse as sp

from bench import matrices

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def grid(nx, ny, seed=None):
    return matrices.generate({"generator": "grid_laplacian_2d",
                              "params": {"nx": nx, "ny": ny}}, seed)


@pytest.mark.parametrize("nx,ny,stencil", [(7, 5, 5), (12, 12, 5)])
def test_grid_matches_spmv_suite(nx, ny, stencil):
    from repro.configs.spmv_suite import grid_laplacian_2d

    lib = grid_laplacian_2d(nx, ny, stencil=stencil)
    mine = grid(nx, ny)
    assert mine.shape == tuple(lib.shape)
    np.testing.assert_array_equal(mine.indptr, np.asarray(lib.row_ptr))
    np.testing.assert_array_equal(mine.indices, np.asarray(lib.col_idx))
    np.testing.assert_array_equal(mine.data, np.asarray(lib.vals))
    assert mine.data.dtype == np.float32 and mine.indices.dtype == np.int32


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_seeded_values_keep_the_structure(seed):
    """Same arrays of structure as the library's grid; symmetric values,
    diagonally dominant and strictly so on the border, that bfloat16 cannot
    hold; the same seed gives the same matrix and another seed another."""
    plain, mine = grid(9, 11), grid(9, 11, seed)
    np.testing.assert_array_equal(mine.indptr, plain.indptr)
    np.testing.assert_array_equal(mine.indices, plain.indices)
    A = sp.csr_matrix((mine.data.astype(np.float64), mine.indices, mine.indptr),
                      shape=mine.shape)
    assert abs(A - A.T).max() == 0
    d = A.diagonal()
    off = np.asarray(abs(A).sum(axis=1)).ravel() - d
    border = np.diff(mine.indptr) < 5
    assert np.all(d - off >= -1e-5 * d)
    assert np.all(d[border] - off[border] >= 1.5 - 1e-5 * d[border])
    assert np.all(A.data[A.data < 0] <= -1) and np.all(A.data[A.data < 0] > -2)
    as_bf16 = mine.data.view(np.uint32) & 0xFFFF
    assert np.mean(as_bf16 != 0) > 0.9
    np.testing.assert_array_equal(grid(9, 11, seed).data, mine.data)
    assert not np.array_equal(grid(9, 11, seed + 1).data, mine.data)


@pytest.mark.parametrize("name,suite_id", [("ecology1", 8)])
def test_configs_are_the_suite_entries_at_published_n(name, suite_id):
    """The configuration's grid is what ``SUITE[id].build(1)`` builds, and its
    stated rows and nnz are what that grid has."""
    from repro.configs.spmv_suite import SUITE

    cfg = json.load(open(os.path.join(BENCH, "configs", f"{name}.json")))
    params = cfg["matrix"]["params"]
    entry = next(e for e in SUITE if e.id == suite_id)
    assert entry.family == "2d_pde"
    side = int(np.sqrt(entry.paper_n))
    assert (params["nx"], params["ny"]) == (side, side)
    n = side * side
    assert cfg["rows"] == n and cfg["nnz"] == n + 4 * side * (side - 1)
    assert cfg["reduced"] == []
    for key in ("rows", "nnz"):
        assert cfg[key] == cfg["published"][key]


def test_unknown_generator_is_an_error():
    with pytest.raises(ModuleNotFoundError):
        matrices.generate({"generator": "no_such_matrix", "params": {}})
