"""Jit'd public wrappers around the Pallas kernels.

``spmv_csrk`` is the paper's tuned SpMV entry point: it takes the CSR-k tile
view (built once at setup from the canonical CSR-k arrays), pads x to the
window grid, launches the kernel and folds in the COO remainder.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.sparse import (
    CSRkTileBuckets,
    CSRkTiles,
    DIAHybridMatrix,
    ELLMatrix,
    SegSumCSR,
    SELLCSTiles,
)
from repro.kernels import ref
from repro.kernels.gather import LANE, round_up
from repro.kernels.spmv_csrk import spmv_csrk_tiles_pallas
from repro.kernels.spmv_diahybrid import spmv_dia_pallas
from repro.kernels.spmv_ell import spmv_ell_pallas
from repro.kernels.spmv_segsum import spmv_segsum_pallas
from repro.kernels.spmv_sellcs import spmv_sellcs_pallas
from repro.obs import annotate, annotated


def _pad_rows(x: jax.Array, target: int) -> jax.Array:
    """Zero-pad x along axis 0 to ``target`` rows ([n] and [n, B] alike).

    Shared padding idiom for both kernel wrappers: the kernels only ever need
    x extended with inert zeros on the leading (column-index) axis; any
    trailing batch dimension rides along unpadded.
    """
    pad = [(0, target - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad)


def _pad_x_to_blocks(x: jax.Array, window: int) -> jax.Array:
    """Pad x so every (win_block, win_block+1) pair addresses valid blocks."""
    n = x.shape[0]
    nblocks = -(-n // window)
    return _pad_rows(x, (nblocks + 1) * window)


def _fold_remainder(y: jax.Array, view, x: jax.Array) -> jax.Array:
    """Add the COO remainder (``rem_row``/``rem_col``/``rem_val``) into y."""
    rem_val = view.rem_val.astype(y.dtype)
    if x.ndim == 2:
        rem_val = rem_val[:, None]
    return y.at[view.rem_row].add(rem_val * x[view.rem_col].astype(y.dtype))


def combine_tile_rows(parts, tile_ids, num_tiles: int, rows_per_tile: int,
                      dtype=None) -> jax.Array:
    """Scatter partial-tile-set kernel outputs back into contiguous rows.

    The Pallas kernels are pure in their tile arrays, so any *subset* of
    tiles can be launched on its own compacted array stack; each launch
    returns ``[T_sub · R (, B)]`` rows in subset order.  This helper places
    every subset's rows at its tiles' home positions — the shared machinery
    behind the slot-bucketed launcher (PR 5) and the distributed layer's
    interior/boundary split launches.

    Tile row ranges are disjoint, so the scatter order cannot change any
    value: the result is bit-for-bit the monolithic launch over the union of
    the subsets.  Ids equal to ``num_tiles`` act as a dump slot for padding
    tiles (uniform-shape SPMD launches pad subsets with inert tiles) and are
    dropped.

    Args:
      parts: per-subset kernel outputs, each ``[T_sub · R]`` or
        ``[T_sub · R, B]``.
      tile_ids: per-subset int32 id arrays (``[T_sub]``), home tile of each
        subset tile; ``num_tiles`` = dump.
      num_tiles: tiles in the combined row space.
      rows_per_tile: R (CSR-k SSR rows; SELL-C-σ chunk height C).
      dtype: output dtype (defaults to ``parts[0].dtype``).

    Returns:
      ``[num_tiles · R (, B)]`` combined rows; uncovered tiles are zero.
    """
    first = parts[0]
    tail = first.shape[1:]
    if dtype is None:
        dtype = first.dtype
    out = jnp.zeros((num_tiles + 1, rows_per_tile) + tail, dtype)
    for y, ids in zip(parts, tile_ids):
        out = out.at[ids].set(y.reshape((ids.shape[0], rows_per_tile) + tail))
    return out[:num_tiles].reshape((num_tiles * rows_per_tile,) + tail)


@annotated("repro.spmv_csrk_tiles")
def spmv_csrk(
    tiles: CSRkTiles,
    x: jax.Array,
    *,
    gather_mode: str = "onehot",
    gather_chunk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """CSR-k SpMV via the Pallas kernel (+ pure-jnp COO remainder pass).

    ``x`` may be a vector ([n]) or a multi-vector block ([n, B]); the batched
    form streams the matrix tiles once for all B right-hand sides.
    """
    with annotate("repro.pad_x"):
        xp = _pad_x_to_blocks(x, tiles.window)
    y = spmv_csrk_tiles_pallas(
        tiles.vals,
        tiles.local_col,
        tiles.local_row,
        tiles.win_block,
        tiles.col_blocks,
        xp,
        tiles.val_scale,
        rows_per_tile=tiles.rows_per_tile,
        window=tiles.window,
        gather_chunk=gather_chunk,
        gather_mode=gather_mode,  # type: ignore[arg-type]
        interpret=interpret,
    )
    y = y[: tiles.shape[0]]
    if tiles.remainder_nnz:
        with annotate("repro.remainder"):
            y = _fold_remainder(y, tiles, x)
    return y


@annotated("repro.spmv_csrk_bucketed")
def spmv_csrk_bucketed(
    buckets: CSRkTileBuckets,
    x: jax.Array,
    *,
    gather_mode: str = "onehot",
    gather_chunk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Slot-bucketed CSR-k SpMV: one Pallas launch per slot bucket.

    Each bucket reuses :func:`spmv_csrk_tiles_pallas` unchanged over its own
    compacted ``[T_b, S_b]`` arrays; bucket outputs are scattered back to the
    global tile rows via ``tile_ids`` and the COO remainder is folded once.
    Because compaction only drops trailing padding slots, the result is
    bit-for-bit identical to :func:`spmv_csrk` on the monolithic view for
    f32 values (pinned in tests/test_tile_buckets.py) — only the HBM bytes
    per launch change.

    ``x`` may be [n] or [n, B], same as :func:`spmv_csrk`.
    """
    R = buckets.rows_per_tile
    with annotate("repro.pad_x"):
        xp = _pad_x_to_blocks(x, buckets.window)
    parts = [
        spmv_csrk_tiles_pallas(
            b.vals,
            b.local_col,
            b.local_row,
            b.win_block,
            b.col_blocks,
            xp,
            b.val_scale,
            rows_per_tile=R,
            window=buckets.window,
            gather_chunk=gather_chunk,
            gather_mode=gather_mode,  # type: ignore[arg-type]
            interpret=interpret,
        )
        for b in buckets.buckets
    ]
    with annotate("repro.combine"):
        y = combine_tile_rows(
            parts, buckets.tile_ids, buckets.num_tiles, R, dtype=x.dtype
        )[: buckets.shape[0]]
    if buckets.remainder_nnz:
        with annotate("repro.remainder"):
            y = _fold_remainder(y, buckets, x)
    return y


@annotated("repro.spmv_sellcs_tiles")
def spmv_sellcs(
    tiles: SELLCSTiles,
    x: jax.Array,
    *,
    gather_mode: str = "onehot",
    gather_chunk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """SELL-C-σ SpMV via the Pallas kernel (+ scatter back to original rows).

    ``x`` may be a vector ([n]) or a multi-vector block ([n, B]).  x is padded
    against the matrix's column extent (a static property of the prepared
    operator) rounded to the 128-lane grid, so the padded size — and hence the
    kernel's compiled signature — does not depend on the caller's vector.
    """
    m, n = tiles.shape
    n_pad = -(-max(n, x.shape[0]) // 128) * 128
    xp = _pad_rows(x, n_pad)
    y_sorted = spmv_sellcs_pallas(
        tiles.vals,
        tiles.col_idx,
        xp,
        tiles.val_scale,
        gather_chunk=gather_chunk,
        gather_mode=gather_mode,
        interpret=interpret,
    )
    # σ-sorted order → original row order; C-alignment pad rows → dump row m
    out = jnp.zeros((m + 1,) + y_sorted.shape[1:], y_sorted.dtype)
    return out.at[tiles.row_perm].set(y_sorted)[:m]


@annotated("repro.spmv_segsum_csr")
def spmv_segsum(
    mat: SegSumCSR,
    x: jax.Array,
    *,
    gather_mode: str = "onehot",
    gather_chunk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Speculative segmented-sum SpMV: Pallas partials + the carry/patch pass.

    The kernel emits [T · R] per-chunk speculative partials; the patch is a
    single scatter-add through ``seg_row``, which sums the fragments of any
    row spanning chunk boundaries (padding segments land in the dump row m
    and are dropped).  ``x`` may be [n] or [n, B]; like SELL-C-σ, x is padded
    against the column extent rounded to the 128-lane grid so the compiled
    signature does not depend on the caller's vector.
    """
    m, n = mat.shape
    n_pad = -(-max(n, x.shape[0]) // 128) * 128
    xp = _pad_rows(x, n_pad)
    partial = spmv_segsum_pallas(
        mat.vals,
        mat.col_idx,
        mat.local_seg,
        xp,
        mat.val_scale,
        segs_per_chunk=mat.segs_per_chunk,
        gather_chunk=gather_chunk,
        gather_mode=gather_mode,
        interpret=interpret,
    )
    out = jnp.zeros((m + 1,) + partial.shape[1:], partial.dtype)
    return out.at[mat.seg_row.reshape(-1)].add(partial)[:m]


@annotated("repro.spmv_diahybrid")
def spmv_diahybrid(
    mat: DIAHybridMatrix,
    x: jax.Array,
    *,
    row_tile: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Partially-diagonal hybrid SpMV: Pallas DIA plane + CSR-oracle remainder.

    x is extended with the kernel's ``lead`` zero margin so every shifted
    diagonal slice is in-range (off-matrix reads pair zero slot values with
    zero margin reads — inert on both sides); the CSR remainder rides the
    CSR segment-sum (``ref.spmv_csr`` / ``ref.spmm_csr``), the hybrid's
    second half, added after the plane in the same order the oracle uses.
    ``row_tile`` is rounded up to a 128-multiple (the kernel's lane-dense
    output block).  ``x`` may be [n] or [n, B].
    """
    m, n = mat.shape
    offs = mat.offsets
    if not offs:
        y = jnp.zeros((m,) + x.shape[1:], jnp.float32).astype(x.dtype)
    else:
        row_tile = min(round_up(row_tile, LANE), round_up(m, LANE))
        m_pad = round_up(m, row_tile)
        lead = max(0, -min(offs))
        span = round_up(max(max(offs) + lead, 1), LANE)
        L = round_up(max(m_pad + span, lead + n), LANE)
        pad = [(lead, L - lead - n)] + [(0, 0)] * (x.ndim - 1)
        x_ext = jnp.pad(x, pad).astype(jnp.float32)
        plane = jnp.pad(mat.diag_vals, ((0, 0), (0, m_pad - m)))
        y = spmv_dia_pallas(
            plane,
            x_ext,
            offsets=offs,
            lead=lead,
            row_tile=row_tile,
            interpret=interpret,
        )[:m].astype(x.dtype)
    if mat.remainder.nnz:
        rem = (
            ref.spmm_csr(mat.remainder, x) if x.ndim == 2
            else ref.spmv_csr(mat.remainder, x)
        )
        y = y + rem.astype(y.dtype)
    return y


@annotated("repro.spmv_ell")
def spmv_ell(mat: ELLMatrix, x: jax.Array, *, row_tile: int = 256,
             interpret: bool | None = None):
    """ELL SpMV via the Pallas baseline kernel (rows padded to the tile)."""
    m = mat.vals.shape[0]
    row_tile = min(row_tile, max(8, m))
    m_pad = -(-m // row_tile) * row_tile
    cols = jnp.pad(mat.col_idx, ((0, m_pad - m), (0, 0)))
    vals = jnp.pad(mat.vals, ((0, m_pad - m), (0, 0)))
    y = spmv_ell_pallas(cols, vals, x, row_tile=row_tile, interpret=interpret)
    return y[:m]


# re-export oracles so callers can flip kernel↔oracle with one import site
spmv_csrk_ref = ref.spmv_csrk_tiles
spmv_ell_ref = ref.spmv_ell
spmv_sellcs_ref = ref.spmv_sellcs
spmv_segsum_ref = ref.spmv_segsum
spmv_diahybrid_ref = ref.spmv_diahybrid
spmm_csr_ref = ref.spmm_csr
