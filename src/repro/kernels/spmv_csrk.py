"""Pallas TPU kernel for CSR-k SpMV (the paper's GPUSpMV-3/3.5, TPU-adapted).

Mapping (DESIGN §2):
  * super-super-rows     → tiles; :data:`TILES_PER_STEP` tiles per grid step
                           (one ``[8, S]`` HBM→VMEM move per tile stream, the
                           sublane-aligned block Mosaic requires), or, where
                           their x windows would not fit in VMEM, the same
                           blocks over :func:`x_tiles_per_step` sub-steps
  * intra-tile nnz slots → lanes
  * x[col_idx] gather    → contiguous banded x-window per tile (two adjacent
                           blocks of ``window`` columns, placed by a
                           scalar-prefetch index map) + one-hot MXU gather
                           over only the window chunks the tile's columns
                           fall in (its ``col_blocks`` row, read from SMEM)
  * rows                 → one-hot MXU reduce into a lane-dense ``[B, R]``
                           output block per tile

The gather and the segmented row reduction are the shared one-hot idiom of
:mod:`repro.kernels.gather` — the TPU-native substitute for the CUDA
per-thread gather and the shared-memory ``temp[]`` tree reduction.

Checked in interpret mode against ``ref.spmv_csrk_tiles`` and
``ref.spmv_csr`` (tests/test_kernels.py) and compiled for v5e at the paper's
published sizes (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import (
    VMEM_CAP, dequant, gather_dtype, pad_tiles, pick_chunk, resolve_interpret,
    round_up, split_f32, tile_rows, vmem_limit,
)

GatherMode = Literal["onehot", "take"]

#: Tiles per grid step: the sublane count, so each tile-stream block is one
#: aligned ``[8, S]`` slab for every value dtype.
TILES_PER_STEP = 8


def _vmem_bytes(S: int, window: int, xrows: int, chunk: int, x_tiles: int) -> int:
    """The kernel's VMEM estimate: double-buffered tile blocks and x blocks
    (``x_tiles`` tiles' two windows of ``xrows`` rows), plus one one-hot slab
    and its product."""
    TB = TILES_PER_STEP
    return (2 * (4 * TB * S * 4 + 2 * x_tiles * max(xrows, 16) * window * 4)
            + 3 * chunk * S * 4)


def x_tiles_per_step(S: int, window: int, xrows: int, chunk: int) -> int:
    """Tiles whose x windows one grid step holds: a divisor of
    :data:`TILES_PER_STEP`.

    All 8 wherever :func:`vmem_limit` can grant the estimate its full room
    (twice it, plus headroom for Mosaic's temporaries, under its cap), so
    every launch that fitted at 8 a step keeps its grid; else the most that
    do, and at least 1.  A wide window (a 3-D stencil's after Band-k) is
    what takes fewer: then each 8-tile block of the tile streams is visited
    over ``8 / x_tiles`` sub-steps, each with its own tiles' windows.
    """
    for x_tiles in (TILES_PER_STEP, 4, 2):
        if 2 * _vmem_bytes(S, window, xrows, chunk, x_tiles) + (8 << 20) <= VMEM_CAP:
            return x_tiles
    return 1


def _kernel(
    win_ref,       # scalar prefetch: [T_pad] int32 window block per tile
    blocks_ref,    # [TB, 1+K] int32 in SMEM: each tile's col_blocks row
    vals_ref,      # [TB, S]
    lc_ref,        # [TB, S]
    lr_ref,        # [TB, S]
    *rest,         # ([scale_ref [TB, G],] 2·X x refs [P·B, W], y_ref [TB·B, Rp])
    tiles: int,
    x_tiles: int,
    batch: int,
    rows: int,
    window: int,
    chunk: int,
    parts: int,
    gather_mode: GatherMode,
    has_scale: bool,
    dot_dtype,
):
    del win_ref  # consumed by the x BlockSpec index maps
    scale_ref = rest[0] if has_scale else None
    x_refs, y_ref = rest[int(has_scale):-1], rest[-1]
    v = dequant(vals_ref[...], None if scale_ref is None else scale_ref[...])
    lc, lr = lc_ref[...], lr_ref[...]
    for j in range(tiles):
        def one_tile(j=j):
            i = j % x_tiles            # this sub-step's x refs hold tile j's window
            y = tile_rows(
                v[j:j + 1], lc[j:j + 1], lr[j:j + 1],
                x_refs[2 * i:2 * i + 2], (0, window),
                rows=rows, chunk=chunk, parts=parts, gather_mode=gather_mode,
                dot_dtype=dot_dtype,
                blocks=(blocks_ref, j),
            )
            y_ref[j * batch:(j + 1) * batch, :] = y.astype(y_ref.dtype)

        if x_tiles == tiles:
            one_tile()
        else:
            pl.when(pl.program_id(1) == j // x_tiles)(one_tile)


@functools.partial(
    jax.jit,
    static_argnames=("rows_per_tile", "window", "gather_chunk", "gather_mode", "interpret"),
)
def spmv_csrk_tiles_pallas(
    vals: jax.Array,       # [T, S]
    local_col: jax.Array,  # [T, S]
    local_row: jax.Array,  # [T, S]
    win_block: jax.Array,  # [T]
    col_blocks: jax.Array,  # [T, 1 + K]
    x_padded: jax.Array,   # [(nblocks+1) * window] or [..., B] — padded by ops.py
    val_scale: jax.Array | None = None,  # [T, S/group] f32, int8 values only
    *,
    rows_per_tile: int,
    window: int,
    gather_chunk: int = 512,
    gather_mode: GatherMode = "onehot",
    interpret: bool | None = None,
) -> jax.Array:
    """Run the CSR-k Pallas kernel over all tiles.

    Args:
      vals / local_col / local_row: [T, S] padded per-SSR tile arrays.
        ``vals`` may be f32, bf16, or int8; int8 requires ``val_scale``.
      win_block: [T] x-window block index per tile (scalar-prefetched).
      col_blocks: [T, 1 + K] int32, the 128-column window blocks each
        tile's real slots read (:attr:`CSRkTiles.col_blocks`); a tile's
        one-hot gather visits only the chunks holding a listed block.
      x_padded: [(nblocks+1)·window] vector or [·, B] block, padded by
        ops.py (or by the distributed layer's per-shard x reconstruction).
      val_scale: optional [T, S/group] f32 per-group scales for int8 values
        (dequantized in-kernel; accumulation stays f32).
      rows_per_tile / window: static tile geometry from :class:`CSRkTiles`.
      gather_chunk: x-window columns per one-hot slab.

    Returns:
      y of [T · R] (resp. [T · R, B]).  A vector is the B = 1 case of the
      block path, so ``op(x)`` and ``op(x[:, None])[:, 0]`` agree bit for
      bit.

    The kernel is pure in the tile arrays and each tile's result depends only
    on its own slots, so the distributed layer can run it unmodified inside
    ``shard_map`` on any subset of tiles — the property that makes the
    sharded operator bit-for-bit equal to the global launch.
    """
    interpret = resolve_interpret(interpret)
    vector = x_padded.ndim == 1
    xT = x_padded[None, :] if vector else x_padded.T               # [B, L]
    B = xT.shape[0]
    xg, parts = split_f32(xT)                                      # [P·B, L]
    T, S = vals.shape
    TB = TILES_PER_STEP
    steps = -(-T // TB)
    vals, local_col, local_row, val_scale = pad_tiles(
        [vals, local_col, local_row, val_scale], TB
    )
    Rp = round_up(rows_per_tile, 8)
    chunk = pick_chunk(window, gather_chunk)
    X = x_tiles_per_step(S, window, parts * B, chunk)
    sub = TB // X

    # one grid step per TB-tile block; with sub > 1 the block stays resident
    # over its sub-steps, and sub-step g holds tiles g·X … g·X + X − 1's x
    if sub == 1:
        grid, block = (steps,), (lambda t, w: (t, 0))
    else:
        grid, block = (steps, sub), (lambda t, g, w: (t, 0))
    tile_spec = pl.BlockSpec((TB, S), block)
    # every row of the last step's table block is real or zero: a zero count
    # visits nothing, where a partial block's unwritten rows could hold any
    in_specs = [pl.BlockSpec((TB, col_blocks.shape[1]), block,
                             memory_space=pltpu.SMEM)] + [tile_spec] * 3
    operands = [jnp.pad(col_blocks, ((0, steps * TB - T), (0, 0))),
                vals, local_col, local_row]
    if val_scale is not None:
        in_specs.append(pl.BlockSpec((TB, val_scale.shape[1]), block))
        operands.append(val_scale)
    for j in range(X):
        # tile t·TB+j (+ g·X) reads window blocks w and w+1 of x
        if sub == 1:
            lo = lambda t, w, j=j: (0, w[t * TB + j])
            hi = lambda t, w, j=j: (0, w[t * TB + j] + 1)
        else:
            lo = lambda t, g, w, j=j: (0, w[t * TB + g * X + j])
            hi = lambda t, g, w, j=j: (0, w[t * TB + g * X + j] + 1)
        in_specs += [pl.BlockSpec((parts * B, window), lo),
                     pl.BlockSpec((parts * B, window), hi)]
        operands += [xg, xg]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((TB * B, Rp), block),
    )
    kernel = functools.partial(
        _kernel, tiles=TB, x_tiles=X, batch=B, rows=Rp, window=window, chunk=chunk,
        parts=parts, gather_mode=gather_mode, has_scale=val_scale is not None,
        dot_dtype=gather_dtype(interpret),
    )
    vmem = _vmem_bytes(S, window, parts * B, chunk, X)
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((steps * TB * B, Rp), x_padded.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(vmem)),
        interpret=interpret,
        name="spmv_csrk",
    )(jnp.pad(win_block, (0, steps * TB - T)), *operands)
    y = y[:T * B].reshape(T, B, Rp)[:, :, :rows_per_tile]
    y = y.transpose(0, 2, 1).reshape(T * rows_per_tile, B)
    return y[:, 0] if vector else y
