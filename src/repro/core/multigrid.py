"""Geometric multigrid as a CG preconditioner: HPCG's V-cycle (``ComputeMG_ref``).

A :class:`Hierarchy` holds one operator per grid, finest first, each built
by :func:`repro.core.spmv.prepare` under the same keyword arguments, so
``format="auto"`` picks every level's backend from its own matrix.  On
HPCG's 27-point stencil the border rows (18, 12 or 8 nonzeros) are a larger
share of a smaller grid, so the row-length variance grows as the grids
shrink: the fine levels take CSR-k and the coarse ones SELL-C-σ
(docs/architecture.md).  Grids coarsen by halving every dimension;
restriction is injection at the coarse points (:func:`injection`) and
prolongation its transpose, as in HPCG.  The caller re-discretises each
coarse matrix on its halved grid, as HPCG does: with injection, a Galerkin
RAP would keep only the diagonal.

The smoother departs from HPCG's symmetric Gauss-Seidel, which is
sequential: ``nu`` weighted-Jacobi sweeps (:func:`~repro.core.solvers.
jacobi_smoother`) with ``omega`` before the coarse correction and ``nu``
after it at every level, and ``nu`` from zero on the coarsest.  A symmetric
smoother with equal pre- and post-smoothing and P = Rᵀ keeps the cycle
symmetric, and ω·λ_max(D⁻¹A) < 2 keeps it positive definite: a valid
preconditioner for ``cg(..., precond=h.vcycle)``.

Telemetry: the spans ``repro.mg.vcycle``, ``repro.mg.level{l}`` (around
level l's own work, not the levels below it), ``repro.mg.smooth``,
``repro.mg.restrict`` and ``repro.mg.prolong``; :func:`hierarchy` sets the
gauges ``prepare/mg.levels``, ``prepare/mg.rows.l{l}``, ``prepare/mg.nnz.l{l}``
and ``prepare/mg.csrk.l{l}`` (1 where level l took CSR-k).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.solvers import jacobi_smoother
from repro.core.spmv import PreparedSpMV, prepare
from repro.obs import annotate, get_registry
from repro.sparse import CSRMatrix


def injection(grid: Sequence[int]) -> np.ndarray:
    """HPCG's ``f2c`` for a grid ``(nx, ny, nz)`` with point (i, j, k) at row
    ``(i·ny + j)·nz + k``: for each point of the halved grid, the fine index
    of (2i, 2j, 2k)."""
    if any(g % 2 for g in grid):
        raise ValueError(f"grid {tuple(grid)} does not halve: every side must be even")
    idx = np.arange(int(np.prod(grid)), dtype=np.int32).reshape(tuple(grid))
    return idx[::2, ::2, ::2].reshape(-1)


def diagonal(A: CSRMatrix) -> np.ndarray:
    """The main diagonal of ``A`` (zero where none is stored)."""
    row_ptr, cols = np.asarray(A.row_ptr), np.asarray(A.col_idx)
    rows = np.repeat(np.arange(A.m, dtype=np.int32), np.diff(row_ptr))
    d = np.zeros(A.m, np.asarray(A.vals).dtype)
    on = rows == cols
    d[rows[on]] = np.asarray(A.vals)[on]
    return d


@dataclasses.dataclass(frozen=True)
class Level:
    """One grid: its operator, its diagonal and, above the coarsest, the
    fine index of each point of the next coarser grid (all in the matrix's
    own ordering; ``op.apply_original`` maps in it)."""

    op: PreparedSpMV
    diag: jax.Array
    f2c: Optional[jax.Array]


@dataclasses.dataclass(frozen=True)
class Hierarchy:
    """Levels finest first, with the smoother's sweeps ``nu`` and weight ``omega``."""

    levels: Tuple[Level, ...]
    nu: int = 2
    omega: float = 0.8

    def vcycle(self, r: jax.Array) -> jax.Array:
        """z ≈ A⁻¹ r by one V-cycle from z = 0 (HPCG's ``ComputeMG``)."""
        with annotate("repro.mg.vcycle"):
            return self._cycle(0, r)

    def _cycle(self, l: int, r: jax.Array) -> jax.Array:
        lev = self.levels[l]
        with annotate(f"repro.mg.level{l}"):
            x = self._smooth(lev, r)
            if lev.f2c is None:
                return x
            with annotate("repro.mg.restrict"):
                rc = (r - lev.op.apply_original(x))[lev.f2c]
        xc = self._cycle(l + 1, rc)
        with annotate(f"repro.mg.level{l}"):
            with annotate("repro.mg.prolong"):
                x = x.at[lev.f2c].add(xc)
            return self._smooth(lev, r, x)

    def _smooth(self, lev: Level, r: jax.Array, x: Optional[jax.Array] = None):
        """``nu`` sweeps from ``x``; from zero the first needs no product."""
        with annotate("repro.mg.smooth"):
            sweeps = self.nu
            if x is None:
                x, sweeps = self.omega * r / lev.diag, sweeps - 1
            return jacobi_smoother(lev.op.apply_original, lev.diag, r, x,
                                   iters=sweeps, omega=self.omega)


def hierarchy(
    levels: Sequence[CSRMatrix],
    f2c: Sequence[np.ndarray],
    *,
    nu: int = 2,
    omega: float = 0.8,
    prepared: Sequence[Optional[PreparedSpMV]] = (),
    **prepare_kwargs,
) -> Hierarchy:
    """The multigrid hierarchy of ``levels`` (finest first).

    Args:
      levels: each grid's matrix, finest first.
      f2c: ``len(levels) - 1`` injection maps: ``f2c[l][i]`` is the index on
        level l of point i of level l + 1 (:func:`injection`).
      nu, omega: the weighted-Jacobi smoother's sweeps and weight.
      prepared: operators the caller already built from the first levels
        with the same ``prepare_kwargs`` (None where to build), so a fine
        level prepared once is not prepared again.
      prepare_kwargs: passed to :func:`~repro.core.spmv.prepare` for every
        level built here.

    Raises:
      ValueError: for maps that do not fit the levels, or ``nu < 1``.
    """
    if len(f2c) != len(levels) - 1:
        raise ValueError(f"{len(levels)} levels need {len(levels) - 1} injection maps, "
                         f"got {len(f2c)}")
    if nu < 1:
        raise ValueError(f"the smoother needs nu >= 1 sweeps, got {nu}")
    for l, m in enumerate(f2c):
        m = np.asarray(m)
        if m.shape != (levels[l + 1].m,) or m.min() < 0 or m.max() >= levels[l].m:
            raise ValueError(f"f2c[{l}] does not map level {l + 1}'s {levels[l + 1].m} "
                             f"points into level {l}'s {levels[l].m}")
    reg = get_registry()
    reg.gauge("prepare", "mg.levels", len(levels), unit="count")
    built = []
    for l, A in enumerate(levels):
        op = prepared[l] if l < len(prepared) else None
        op = prepare(A, **prepare_kwargs) if op is None else op
        reg.gauge("prepare", f"mg.rows.l{l}", A.m, unit="count")
        reg.gauge("prepare", f"mg.nnz.l{l}", A.nnz, unit="count")
        reg.gauge("prepare", f"mg.csrk.l{l}", float(op.backend == "csrk"), unit="flag")
        built.append(Level(op=op, diag=jnp.asarray(diagonal(A)),
                           f2c=jnp.asarray(f2c[l]) if l < len(f2c) else None))
    return Hierarchy(levels=tuple(built), nu=nu, omega=omega)
