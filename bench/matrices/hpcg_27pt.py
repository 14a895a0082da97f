"""HPCG's 27-point operator on an nx × ny × nz grid: the hpcg family.

A copy of the library's ``repro.configs.spmv_suite.hpcg_27pt`` in plain
numpy (HPCG's ``GenerateProblem_ref.cpp``), kept here so that a change to
the library cannot move the benchmark's matrices; ``bench/tests`` checks
that both give the same arrays.  Every point couples to each of its up to 26
neighbours with -1 and to itself with 26.  These values are exact in
bfloat16, and HPCG fixes them, so the seed draws none: a run's checks rest
on its vectors.

The matrix carries its grid (:class:`GridCsr`), which a multigrid driver
halves for the coarse levels; point (i, j, k) is row ``(i·ny + j)·nz + k``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from bench.matrices import Csr


@dataclasses.dataclass(frozen=True)
class GridCsr(Csr):
    """A :class:`~bench.matrices.Csr` of a structured grid ``(nx, ny, nz)``."""

    grid: tuple


def nnz(grid) -> int:
    """Nonzeros of the operator on ``grid``: (3n − 2)³ on an n³ grid."""
    return int(np.prod([3 * g - 2 for g in grid]))


def build(nx: int, ny: int, nz: int, seed: Optional[int] = None) -> GridCsr:
    del seed  # HPCG's values are fixed
    idx = np.arange(nx * ny * nz, dtype=np.int32).reshape(nx, ny, nz)
    pad = np.pad(idx, 1, constant_values=-1)
    # the 27 offsets in lexicographic order are the columns in ascending order
    cols = np.stack([pad[1 + di:1 + di + nx, 1 + dj:1 + dj + ny, 1 + dk:1 + dk + nz]
                     for di in (-1, 0, 1) for dj in (-1, 0, 1) for dk in (-1, 0, 1)],
                    axis=-1).reshape(-1, 27)
    vals = np.where(np.arange(27) == 13, 26.0, -1.0).astype(np.float32)
    keep = cols >= 0
    indptr = np.concatenate([[0], np.cumsum(keep.sum(axis=1))]).astype(np.int32)
    n = idx.size
    return GridCsr(indptr, cols[keep], np.broadcast_to(vals, cols.shape)[keep].copy(),
                   (n, n), grid=(nx, ny, nz))
