"""The f32 row bound every SpMV result is held to against float64.

A row of k nonzeros sums k f32 products.  Each product rounds once and each
addition once, in whatever order the kernel adds them (slot groups on the
MXU, a COO remainder, a bucket combine), so the error of row i is at most
about ``k_i · 2⁻²⁴ · Σ_j |a_ij x_j|``.  The extra 32 ulps cover the few
fixed passes outside the row sum: the combine of partial sums and the
exact-in-bf16 split of f32 operands for the one-hot matmuls.
"""
import numpy as np
import scipy.sparse as sp

EPS32 = 2.0 ** -24


def assert_rows_within_f32_bound(A, x, y):
    """``y`` (f32, [m]) is ``A @ x`` to within the f32 row bound."""
    rp = np.asarray(A.row_ptr)
    a64 = sp.csr_matrix(
        (np.asarray(A.vals, np.float64), np.asarray(A.col_idx), rp), shape=A.shape
    )
    x64 = np.asarray(x, np.float64)
    ref = a64 @ x64
    mag = abs(a64) @ np.abs(x64)
    k = np.diff(rp)
    bound = (k + 32) * EPS32 * mag
    err = np.abs(np.asarray(y, np.float64) - ref)
    bad = np.flatnonzero(err > bound)
    assert bad.size == 0, (
        f"{bad.size} of {A.m} rows outside the f32 bound; worst row "
        f"{bad[np.argmax(err[bad] / np.maximum(bound[bad], 1e-300))]}: "
        f"err {err[bad].max():.3e}, bound {bound[bad].min():.3e}"
    )
