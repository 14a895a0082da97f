"""Pallas TPU kernel for SELL-C-σ SpMV (the irregular-matrix path).

Mapping, following the CSR-k kernel's idiom (spmv_csrk.py):
  * C-row chunks      → tiles; :data:`TILES_PER_STEP` chunks per grid step
  * a chunk's [C, W] slots → one ``[1, C·W]`` lane vector, row r owning lanes
    ``[r·W, (r+1)·W)``
  * x[col_idx] gather → one-hot matmuls on the MXU, and the per-row sum →
    the shared one-hot reduce (:mod:`repro.kernels.gather`)

Unlike CSR-k there is no Band-k window guarantee: irregular matrices scatter
columns anywhere, so x is held whole in VMEM and every chunk's gather sweeps
all of it.  That bounds the usable n (:data:`~repro.kernels.gather.
WHOLE_X_MAX_COLS`, enforced by ``prepare``) — exactly the scalability
pressure the banded CSR-k path avoids; the registry routes accordingly.

Checked in interpret mode against ``ref.spmv_sellcs``
(tests/test_sparse_registry.py sweeps shapes and dtypes) and compiled for
v5e at the whole-x limit (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import (
    dequant, gather_dtype, pick_chunk, resolve_interpret, split_f32, tile_rows,
    vmem_limit,
)

#: Chunks per grid step (C = 8 rows each → 64 output rows per step).
TILES_PER_STEP = 8


def _kernel(
    vals_ref,   # [TB, C, W]
    col_ref,    # [TB, C, W]
    *rest,      # ([scale_ref [TB, C, W/G],] x_ref [P·B, n_pad], y_ref [TB·B, C])
    tiles: int,
    batch: int,
    chunk: int,
    parts: int,
    gather_mode: str,
    has_scale: bool,
    dot_dtype,
):
    scale_ref = rest[0] if has_scale else None
    x_ref, y_ref = rest[-2:]
    C, W = vals_ref.shape[1:]
    lr = jnp.concatenate(
        [jnp.full((1, W), r, jnp.int32) for r in range(C)], axis=1
    )                                                              # [1, C·W]
    for j in range(tiles):
        v = dequant(vals_ref[j], None if scale_ref is None else scale_ref[j])
        cols = col_ref[j]
        v = jnp.concatenate([v[r:r + 1] for r in range(C)], axis=1)
        lc = jnp.concatenate([cols[r:r + 1] for r in range(C)], axis=1)
        y = tile_rows(
            v, lc, lr, (x_ref,), (0,),
            rows=C, chunk=chunk, parts=parts, gather_mode=gather_mode,
            dot_dtype=dot_dtype,
        )
        y_ref[j * batch:(j + 1) * batch, :] = y.astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("gather_chunk", "gather_mode", "interpret")
)
def spmv_sellcs_pallas(
    vals: jax.Array,     # [T, C, W]
    col_idx: jax.Array,  # [T, C, W]
    x_padded: jax.Array, # [n_pad] or [n_pad, B] — padded to a 128 multiple by ops.py
    val_scale: jax.Array | None = None,  # [T, C, W/group] f32, int8 values only
    *,
    gather_chunk: int = 512,
    gather_mode: str = "onehot",
    interpret: bool | None = None,
) -> jax.Array:
    """Run the SELL-C-σ kernel over all chunks.

    Args:
      vals / col_idx: [T, C, W] uniform-width chunk arrays (padding slots
        carry val 0 / col 0 and are inert).  ``vals`` may be f32, bf16, or
        int8; int8 requires ``val_scale`` (per-lane-group f32 scales,
        dequantized in-kernel with f32 accumulation).
      x_padded: [n_pad] vector or [n_pad, B] block, padded to a 128 multiple
        by ops.py (or by the distributed layer's per-shard reconstruction).

    Returns:
      y of [T · C] (resp. [T · C, B]) in σ-sorted row order — the caller
      (ops.py, or the sharded operator after reassembly) scatters back to
      the original ordering via ``row_perm``.  A vector is the B = 1 case of
      the block path.

    Like the CSR-k kernel, this is pure in the chunk arrays: the distributed
    layer runs it unmodified inside ``shard_map`` over a contiguous slice of
    chunks (smaller T, identical statics).
    """
    interpret = resolve_interpret(interpret)
    vector = x_padded.ndim == 1
    xT = x_padded[None, :] if vector else x_padded.T               # [B, n_pad]
    B, n_pad = xT.shape
    xg, parts = split_f32(xT)
    T, C, W = vals.shape
    TB = TILES_PER_STEP
    steps = -(-T // TB)
    chunk = pick_chunk(n_pad, gather_chunk)

    in_specs = [
        pl.BlockSpec((TB, C, W), lambda t: (t, 0, 0)),
        pl.BlockSpec((TB, C, W), lambda t: (t, 0, 0)),
    ]
    operands = [vals, col_idx]
    if val_scale is not None:
        in_specs.append(
            pl.BlockSpec((TB, C, val_scale.shape[2]), lambda t: (t, 0, 0))
        )
        operands.append(val_scale)
    kernel = functools.partial(
        _kernel, tiles=TB, batch=B, chunk=chunk, parts=parts,
        gather_mode=gather_mode, has_scale=val_scale is not None,
        dot_dtype=gather_dtype(interpret),
    )
    vmem = (2 * 3 * TB * C * W * 4 + 2 * max(parts * B, 16) * n_pad * 4
            + 3 * chunk * C * W * 4)
    y = pl.pallas_call(
        kernel,
        grid=(steps,),
        in_specs=in_specs + [pl.BlockSpec((parts * B, n_pad), lambda t: (0, 0))],
        out_specs=pl.BlockSpec((TB * B, C), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((steps * TB * B, C), x_padded.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(vmem)),
        interpret=interpret,
        name="spmv_sellcs",
    )(*operands, xg)
    y = y[:T * B].reshape(T, B, C).transpose(0, 2, 1).reshape(T * C, B)
    return y[:, 0] if vector else y
