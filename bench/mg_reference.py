"""The plain reference of the multigrid cell: HPCG's V-cycle and PCG in float64.

Imports nothing of the library and takes nothing the library made: the level
matrices come from ``bench.matrices.hpcg_27pt`` at the halved grids, and the
injection maps are built here.  The V-cycle is HPCG's ``ComputeMG_ref`` with
the cell's smoother: ``nu`` weighted-Jacobi sweeps with ``omega`` before the
coarse correction and ``nu`` after it, the first from zero; the coarsest
level only smooths, ``nu`` sweeps from zero.  Restriction is injection of the
residual, ``rc[i] = (r - A x)[f2c[i]]``, and prolongation ``x[f2c[i]] +=
xc[i]``.

:func:`vcycle` takes each level's product as a callable and the scatter-add
as a function, so the control (``bench/mg_control.py``) runs the same cycle
in bfloat16 under ``jax.numpy``.
"""
from __future__ import annotations

from typing import Callable, List, NamedTuple, Optional

import numpy as np

from bench.matrices import hpcg_27pt
from bench.reference import to_scipy


def f2c(grid) -> np.ndarray:
    """Fine index of point (2i, 2j, 2k) for each (i, j, k) of the halved grid."""
    nx, ny, nz = grid
    fine = np.arange(nx * ny * nz).reshape(nx, ny, nz)
    return fine[::2, ::2, ::2].reshape(-1)


def grids(fine, levels: int) -> List[tuple]:
    """The grids finest first, each side halved per level."""
    if any(g % (1 << (levels - 1)) for g in fine):
        raise ValueError(f"grid {tuple(fine)} does not halve {levels - 1} times")
    return [tuple(g >> l for g in fine) for l in range(levels)]


class Level(NamedTuple):
    matvec: Callable
    diag: object
    f2c: Optional[object]


def _add_at(x, idx, v):
    x = x.copy()
    x[idx] += v
    return x


def vcycle(levels, r, *, nu: int, omega: float, add_at=_add_at, l: int = 0):
    """z ≈ A⁻¹ r by one V-cycle from z = 0."""
    lev = levels[l]
    x = omega * r / lev.diag
    for _ in range(nu - 1):
        x = x + omega * (r - lev.matvec(x)) / lev.diag
    if lev.f2c is None:
        return x
    rc = (r - lev.matvec(x))[lev.f2c]
    x = add_at(x, lev.f2c, vcycle(levels, rc, nu=nu, omega=omega, add_at=add_at, l=l + 1))
    for _ in range(nu):
        x = x + omega * (r - lev.matvec(x)) / lev.diag
    return x


class MgReference:
    """Float64 levels of the cell's matrix: the fine one and its halvings."""

    def __init__(self, mat, levels: int):
        self.grids = grids(mat.grid, levels)
        mats = [mat] + [hpcg_27pt.build(*g) for g in self.grids[1:]]
        self.A = [to_scipy(m) for m in mats]
        self.levels = [Level(A.__matmul__, A.diagonal(),
                             f2c(g) if l + 1 < levels else None)
                       for l, (A, g) in enumerate(zip(self.A, self.grids))]

    def vcycle(self, r, *, nu: int, omega: float) -> np.ndarray:
        return vcycle(self.levels, np.asarray(r, np.float64), nu=nu, omega=omega)

    def pcg(self, b, *, iters: int, nu: int, omega: float) -> np.ndarray:
        """``iters`` iterations of V-cycle-preconditioned CG from x = 0."""
        A = self.A[0]
        b = np.asarray(b, np.float64)
        x = np.zeros_like(b)
        r = b.copy()
        z = self.vcycle(r, nu=nu, omega=omega)
        p, rz = z, r @ z
        for _ in range(iters):
            Ap = A @ p
            alpha = rz / (p @ Ap)
            x = x + alpha * p
            r = r - alpha * Ap
            z = self.vcycle(r, nu=nu, omega=omega)
            rz, rz_old = r @ z, rz
            p = z + (rz / rz_old) * p
        return x


def vcycle_error(z, z_ref) -> float:
    """‖z − z_ref‖∞ / ‖z_ref‖∞ (NaN stays NaN; a wrong shape is inf)."""
    z = np.asarray(z, np.float64)
    if z.shape != z_ref.shape:
        return float("inf")
    err = np.max(np.abs(z - z_ref)) / np.max(np.abs(z_ref))
    return float(err) if np.isfinite(err) else float("nan")
