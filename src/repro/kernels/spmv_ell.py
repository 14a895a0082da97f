"""Pallas TPU kernel for ELL SpMV — the GPU-heritage baseline (paper Sec. 2.3).

ELL is the format the paper cites as the historical GPU favourite; it is kept
here as the baseline the CSR-k kernel is compared to.
The kernel tiles the m×kmax dense slab over rows; x is not windowed (ELL has
no banding guarantee), so x must fit VMEM — exactly the ELL scalability
weakness the paper describes, now visible as a VMEM constraint.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gather import resolve_interpret


def _kernel(cols_ref, vals_ref, x_ref, y_ref):
    cols = cols_ref[...]                    # [R, K]
    vals = vals_ref[...]                    # [R, K]
    x = x_ref[...]                          # [n]
    gathered = jnp.take(x, cols.reshape(-1), axis=0).reshape(cols.shape)
    y_ref[...] = jnp.sum(
        vals.astype(jnp.float32) * gathered.astype(jnp.float32), axis=1
    ).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def spmv_ell_pallas(
    col_idx: jax.Array,   # [m_padded, kmax]
    vals: jax.Array,      # [m_padded, kmax]
    x: jax.Array,         # [n]
    *,
    row_tile: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    m, k = vals.shape
    assert m % row_tile == 0, "pad rows to a multiple of row_tile"
    n = x.shape[0]
    return pl.pallas_call(
        _kernel,
        grid=(m // row_tile,),
        in_specs=[
            pl.BlockSpec((row_tile, k), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, k), lambda i: (i, 0)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((row_tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((m,), x.dtype),
        interpret=interpret,
    )(col_idx, vals, x)
