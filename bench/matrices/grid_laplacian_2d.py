"""2-D 5-point grid Laplacian: the ecology family.

The structure is a copy of the library's
``repro.configs.spmv_suite.grid_laplacian_2d`` (5-point) in plain numpy,
kept here so that a change to the library cannot move the benchmark's
matrices; ``bench/tests`` checks that both give the same arrays.

Without a seed the values are the library's: -1 off the diagonal, 4 on it.
With a seed each grid link gets its own conductance ``w = 1 + u``, ``u``
uniform in [0, 1), stored as -w on both sides (symmetric), and each
diagonal is the sum of its row's conductances plus ``1.5`` (their mean) for
every missing neighbour of a border node: diagonally dominant, strictly on
the border, on a connected grid, so the matrix is SPD.  These values are
not exact in bfloat16, so a product that stores or reads them in a lower
precision shows.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from bench.matrices import Csr, csr_from_coo

DEGREE = 4


def build(nx: int, ny: int, seed: Optional[int] = None) -> Csr:
    n = nx * ny
    idx = np.arange(n).reshape(nx, ny)
    pairs = [(idx[:-1, :], idx[1:, :]), (idx[:, :-1], idx[:, 1:])]
    r = np.concatenate([a.reshape(-1) for a, _ in pairs])
    c = np.concatenate([b.reshape(-1) for _, b in pairs])
    if seed is None:
        w, mean = np.ones(len(r)), 1.0
    else:
        w, mean = 1.0 + np.random.default_rng([int(seed), 100]).random(len(r)), 1.5
    degree = np.bincount(r, minlength=n) + np.bincount(c, minlength=n)
    diag_vals = (np.bincount(r, w, minlength=n) + np.bincount(c, w, minlength=n)
                 + (DEGREE - degree) * mean)
    diag = np.arange(n)
    rows = np.concatenate([r, c, diag])
    cols = np.concatenate([c, r, diag])
    vals = np.concatenate([-w, -w, diag_vals])
    # every (row, col) pair occurs once: grid links are distinct and never diagonal
    return csr_from_coo(rows, cols, vals, (n, n))
