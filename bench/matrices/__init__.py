"""The benchmark's own matrix generators, found by name.

A configuration names its generator (``"generator": "grid_laplacian_2d"``)
and the generator's parameters; ``matrices/<generator>.py`` defines
``build(**params) -> Csr``.  Generators are plain numpy: they import nothing
of the library, so the reference and the program see the same matrix however
the library changes.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np


@dataclasses.dataclass(frozen=True)
class Csr:
    """A CSR matrix on the host: int32 ``indptr`` / ``indices``, f32 ``data``."""

    indptr: np.ndarray
    indices: np.ndarray
    data: np.ndarray
    shape: tuple

    @property
    def nnz(self) -> int:
        return int(self.data.shape[0])

    def row_ids(self) -> np.ndarray:
        return np.repeat(np.arange(self.shape[0], dtype=np.int32), np.diff(self.indptr))


def csr_from_coo(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray, shape) -> Csr:
    """Row-major CSR of COO triplets whose (row, col) pairs are unique."""
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    indptr = np.zeros(shape[0] + 1, np.int32)
    np.add.at(indptr, rows + 1, 1)
    np.cumsum(indptr, out=indptr)
    return Csr(indptr, cols.astype(np.int32), vals.astype(np.float32), tuple(shape))


def generate(spec: dict, seed=None) -> Csr:
    """Build the matrix a configuration describes: ``{"generator", "params"}``,
    with its values drawn from ``seed`` (None: the generator's fixed values)."""
    name = spec["generator"]
    if not name.isidentifier():
        raise ValueError(f"generator name {name!r} is not a module name")
    module = importlib.import_module(f"{__name__}.{name}")
    return module.build(**spec.get("params", {}), seed=seed)
