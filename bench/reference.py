"""The plain reference that decides ``correct``, and its lower-precision control.

Imports nothing of the library and takes nothing the library made: the
matrix comes from ``bench.matrices`` and the vectors from the run's seed.

* ``row_error``: the widest gap of an SpMV answer from the float64 product,
  row by row, as a share of that row's ``sum_j |a_ij x_j|`` -- the scale an
  f32 accumulation of the row rounds against.
* ``residual``: the float64 relative residual ``|b - A x| / |b|`` of a solve's
  returned iterate.
* ``control_matvec``: the same product with the matrix values and x rounded
  to bfloat16 (products summed in f32): the next precision below the
  configuration's f32, which a comparison worth its name has to reject.
  With ``round_x=False`` only the values are rounded: the value-byte cut a
  later change might be tempted by.  ``plain_cg`` is the reference solver
  that runs it in the CG cell's control.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
import scipy.sparse as sp

from bench.matrices import Csr


def to_scipy(mat: Csr) -> sp.csr_matrix:
    return sp.csr_matrix((mat.data.astype(np.float64), mat.indices, mat.indptr),
                         shape=mat.shape)


class Reference:
    """Float64 products of one matrix, with the row scales they are judged on."""

    def __init__(self, mat: Csr):
        self.A = to_scipy(mat)
        self.absA = abs(self.A)

    def matvec(self, x: np.ndarray) -> np.ndarray:
        return self.A @ np.asarray(x, np.float64)

    def row_error(self, x: np.ndarray, y: np.ndarray) -> float:
        """max_i |y_i - (Ax)_i| / sum_j |a_ij x_j| (NaN stays NaN)."""
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        if y.shape != (self.A.shape[0],) + x.shape[1:]:
            return float("inf")
        scale = np.maximum(self.absA @ np.abs(x), np.finfo(np.float64).tiny)
        err = np.abs(y - self.A @ x) / scale
        return float(np.max(err)) if np.all(np.isfinite(err)) else float("nan")

    def residual(self, x: np.ndarray, b: np.ndarray) -> float:
        x = np.asarray(x, np.float64)
        b = np.asarray(b, np.float64)
        if x.shape != b.shape:
            return float("inf")
        return float(np.linalg.norm(b - self.A @ x) / np.linalg.norm(b))


def control_matvec(mat: Csr, round_x: bool = True):
    """``x -> A x`` with the values (and x) rounded to bfloat16, products summed in f32.

    Runs on the default device (the chip, in a chip run), at the cell's size.
    Not jitted, so a caller that traces it (the CG driver) lifts the matrix
    arrays out as arguments.
    """
    import jax
    import jax.numpy as jnp

    vals = jnp.asarray(mat.data).astype(jnp.bfloat16).astype(jnp.float32)
    cols = jnp.asarray(mat.indices)
    rows = jnp.asarray(mat.row_ids())
    m = mat.shape[0]

    def apply(x):
        xb = x.astype(jnp.bfloat16).astype(jnp.float32) if round_x else x
        prod = vals.reshape((-1,) + (1,) * (x.ndim - 1)) * xb[cols]
        return jax.ops.segment_sum(prod, rows, num_segments=m)

    return apply


class Solve(NamedTuple):
    x: object
    iters: object


def plain_cg(matvec, b, *, tol: float, maxiter: int) -> Solve:
    """Textbook conjugate gradients from x0 = 0 to ``|r| <= tol |b|`` (jax.numpy)."""
    import jax
    import jax.numpy as jnp

    tol2 = tol ** 2 * jnp.vdot(b, b)

    def cond(s):
        return jnp.logical_and(s[3] > tol2, s[4] < maxiter)

    def body(s):
        x, r, p, rs, k = s
        ap = matvec(p)
        alpha = rs / jnp.vdot(p, ap)
        x = x + alpha * p
        r = r - alpha * ap
        rs_new = jnp.vdot(r, r)
        return x, r, r + (rs_new / rs) * p, rs_new, k + 1

    x, _, _, _, k = jax.lax.while_loop(
        cond, body, (jnp.zeros_like(b), b, b, jnp.vdot(b, b), 0))
    return Solve(x, k)
