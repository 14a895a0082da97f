"""Pure-jnp oracles for every kernel in this package.

These are the correctness references the Pallas kernels are swept against
(tests/test_kernels.py) and the "plain CSR" baseline the paper compares
formats to (its cuSPARSE/MKL CSR role).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sparse import (
    BCSRMatrix,
    COOMatrix,
    CSRMatrix,
    CSRkMatrix,
    CSRkTileBuckets,
    CSRkTiles,
    DIAHybridMatrix,
    ELLMatrix,
    SegSumCSR,
    SELLCSMatrix,
    SELLCSTiles,
)
from repro.obs import annotated


def _tile_vals_f32(vals: jax.Array, val_scale) -> jax.Array:
    """Tile values as f32: upcast bf16/f32, dequantize int8 grouped scales.

    Mirrors the in-kernel dequantization (``repro.kernels.gather.dequant``):
    scale groups run along the last (slot/lane) axis, one f32 scale per
    ``vals.shape[-1] // val_scale.shape[-1]`` slots.
    """
    v = vals.astype(jnp.float32)
    if val_scale is not None:
        g = v.shape[-1] // val_scale.shape[-1]
        v = v * jnp.repeat(val_scale, g, axis=-1, total_repeat_length=v.shape[-1])
    return v


def spmv_dense(dense: jax.Array, x: jax.Array) -> jax.Array:
    return dense @ x


def spmv_coo(mat: COOMatrix, x: jax.Array) -> jax.Array:
    """COO SpMV: scatter-add (the paper's 'needs atomics' baseline)."""
    contrib = mat.vals * x[mat.col_idx]
    return jnp.zeros((mat.shape[0],), contrib.dtype).at[mat.row_idx].add(contrib)


@annotated("repro.oracle.spmv_csr")
def spmv_csr(mat: CSRMatrix, x: jax.Array) -> jax.Array:
    """Row-segmented CSR SpMV — the canonical oracle."""
    rows = jnp.repeat(
        jnp.arange(mat.m, dtype=jnp.int32),
        mat.row_lengths(),
        total_repeat_length=mat.nnz,
    )
    contrib = mat.vals * x[mat.col_idx]
    return jax.ops.segment_sum(contrib, rows, num_segments=mat.m)


def spmv_csrk_loops(mat: CSRkMatrix, x: jax.Array) -> jax.Array:
    """Direct transcription of the paper's Listing 1 (CSR-3 CPU kernel).

    Nested SSR→SR→row→nnz loops via fori_loop; slow under jit but a faithful
    structural oracle for the hierarchy semantics.
    """
    row_ptr, col_idx, vals = mat.row_ptr, mat.col_idx, mat.vals
    sr_ptr, ssr_ptr = mat.sr_ptr, mat.ssr_ptr

    def row_body(k, y):
        r_start, r_end = row_ptr[k], row_ptr[k + 1]

        def nnz_body(l, temp):
            return temp + vals[l] * x[col_idx[l]]

        temp = jax.lax.fori_loop(r_start, r_end, nnz_body, jnp.zeros((), vals.dtype))
        return y.at[k].set(temp)

    def sr_body(j, y):
        return jax.lax.fori_loop(sr_ptr[j], sr_ptr[j + 1], row_body, y)

    def ssr_body(i, y):
        return jax.lax.fori_loop(ssr_ptr[i], ssr_ptr[i + 1], sr_body, y)

    y0 = jnp.zeros((mat.m,), vals.dtype)
    return jax.lax.fori_loop(0, mat.num_ssr, ssr_body, y0)


def spmv_ell(mat: ELLMatrix, x: jax.Array) -> jax.Array:
    """ELL SpMV: dense gather + row sum (paper Sec. 2.3)."""
    return jnp.sum(mat.vals * x[mat.col_idx], axis=1)


def spmv_bcsr(mat: BCSRMatrix, x: jax.Array) -> jax.Array:
    """BCSR SpMV: per-block dense matvec + segmented add."""
    bR, bC = mat.block_shape
    mb = int(mat.block_row_ptr.shape[0]) - 1
    nblocks = int(mat.blocks.shape[0])
    lengths = mat.block_row_ptr[1:] - mat.block_row_ptr[:-1]
    brow = jnp.repeat(
        jnp.arange(mb, dtype=jnp.int32), lengths, total_repeat_length=nblocks
    )
    xb = x.reshape(-1, bC)[mat.block_col_idx]            # [nblocks, bC]
    contrib = jnp.einsum("brc,bc->br", mat.blocks, xb)    # [nblocks, bR]
    yb = jax.ops.segment_sum(contrib, brow, num_segments=mb)
    return yb.reshape(-1)[: mat.shape[0]]


@annotated("repro.oracle.spmv_csrk_tiles")
def spmv_csrk_tiles(tiles: CSRkTiles, x: jax.Array) -> jax.Array:
    """Oracle for the padded-tile view consumed by the Pallas kernel.

    Computes, per tile t: y[t·R : (t+1)·R] = Σ_s vals[t,s] · x[win+lc[t,s]]
    segment-summed by local_row, plus the COO remainder.  ``x`` may carry a
    trailing batch dimension ([n, B] → [m, B]).
    """
    T, S = tiles.vals.shape
    R, W = tiles.rows_per_tile, tiles.window
    n = tiles.shape[1]
    vals = _tile_vals_f32(tiles.vals, tiles.val_scale).astype(x.dtype)
    # absolute columns, clamped (padding slots have val 0 so clamping is inert)
    abs_col = jnp.minimum(
        tiles.win_block[:, None] * W + tiles.local_col, n - 1
    )
    seg = tiles.local_row + (jnp.arange(T, dtype=jnp.int32) * R)[:, None]
    if x.ndim == 2:
        contrib = vals[..., None] * x[abs_col]             # [T, S, B]
        y = jax.ops.segment_sum(
            contrib.reshape(T * S, -1), seg.reshape(-1), num_segments=T * R
        )
        y = y[: tiles.shape[0]]
        if tiles.remainder_nnz:
            y = y.at[tiles.rem_row].add(tiles.rem_val[:, None] * x[tiles.rem_col])
        return y
    contrib = vals * x[abs_col]                            # [T, S]
    y = jax.ops.segment_sum(contrib.reshape(-1), seg.reshape(-1), num_segments=T * R)
    y = y[: tiles.shape[0]]
    if tiles.remainder_nnz:
        y = y.at[tiles.rem_row].add(tiles.rem_val * x[tiles.rem_col])
    return y


@annotated("repro.oracle.spmv_csrk_buckets")
def spmv_csrk_buckets(buckets: CSRkTileBuckets, x: jax.Array) -> jax.Array:
    """Oracle for the slot-bucketed tile view: per-bucket tile oracle runs,
    scattered back to global tile rows, COO remainder folded once."""
    R = buckets.rows_per_tile
    tail = x.shape[1:]
    y_tiles = jnp.zeros((buckets.num_tiles, R) + tail, x.dtype)
    for b, ids in zip(buckets.buckets, buckets.tile_ids):
        y_b = spmv_csrk_tiles(b, x)
        y_tiles = y_tiles.at[ids].set(y_b.reshape((b.num_tiles, R) + tail))
    y = y_tiles.reshape((buckets.num_tiles * R,) + tail)[: buckets.shape[0]]
    if buckets.remainder_nnz:
        rem_val = buckets.rem_val
        if x.ndim == 2:
            rem_val = rem_val[:, None]
        y = y.at[buckets.rem_row].add(rem_val * x[buckets.rem_col])
    return y


@annotated("repro.oracle.spmv_sellcs_tiles")
def spmv_sellcs_tiles(tiles: SELLCSTiles, x: jax.Array) -> jax.Array:
    """Oracle for the uniform-width SELL-C-σ Pallas view (value-dtype aware).

    The canonical-container oracle (:func:`spmv_sellcs`) always runs f32;
    this one consumes the same compressed [T, C, W] arrays the kernel does,
    so mixed-precision tests can pin kernel == oracle exactly.
    """
    m, n = tiles.shape
    vals = _tile_vals_f32(tiles.vals, tiles.val_scale).astype(x.dtype)
    cols = jnp.minimum(tiles.col_idx, max(n, x.shape[0]) - 1)
    if x.ndim == 2:
        contrib = vals[..., None] * x[cols]                # [T, C, W, B]
        y_sorted = jnp.sum(contrib, axis=2).reshape(-1, x.shape[1])
        out = jnp.zeros((m + 1, x.shape[1]), y_sorted.dtype)
        return out.at[tiles.row_perm].set(y_sorted)[:m]
    contrib = vals * x[cols]                               # [T, C, W]
    y_sorted = jnp.sum(contrib, axis=2).reshape(-1)
    out = jnp.zeros((m + 1,), y_sorted.dtype)
    return out.at[tiles.row_perm].set(y_sorted)[:m]


@annotated("repro.oracle.spmv_sellcs_slots")
def spmv_sellcs(mat: SELLCSMatrix, x: jax.Array) -> jax.Array:
    """SELL-C-σ SpMV oracle over the canonical flat slot arrays.

    Per slot: contrib = vals · x[col]; slots are segment-summed by their
    σ-sorted row id, then scattered back to the original row order via
    ``row_perm`` (padding rows land in the dump row m and are dropped).
    ``x`` may carry a trailing batch dimension ([n, B] → [m, B]).
    """
    m = mat.shape[0]
    if x.ndim == 2:
        contrib = mat.vals[:, None] * x[mat.col_idx]       # [slots, B]
        y_sorted = jax.ops.segment_sum(
            contrib, mat.slot_row, num_segments=mat.m_pad
        )
        out = jnp.zeros((m + 1, x.shape[1]), contrib.dtype)
        return out.at[mat.row_perm].set(y_sorted)[:m]
    contrib = mat.vals * x[mat.col_idx]
    y_sorted = jax.ops.segment_sum(
        contrib, mat.slot_row, num_segments=mat.m_pad
    )
    out = jnp.zeros((m + 1,), contrib.dtype)
    return out.at[mat.row_perm].set(y_sorted)[:m]


@annotated("repro.oracle.spmv_segsum_csr")
def spmv_segsum(mat: SegSumCSR, x: jax.Array) -> jax.Array:
    """Speculative segmented-sum oracle (value-dtype aware).

    Per chunk t: the slot contributions are segment-summed by local segment
    id into [T, R] speculative partials — exactly what the Pallas kernel
    emits — then the carry/patch pass scatter-adds every partial to its
    segment's global row, summing the fragments of rows that span chunks
    (padding segments land in the dump row m and are dropped).  ``x`` may
    carry a trailing batch dimension ([n, B] → [m, B]).
    """
    m = mat.shape[0]
    T, S = mat.vals.shape
    R = mat.segs_per_chunk
    vals = _tile_vals_f32(mat.vals, mat.val_scale).astype(x.dtype)
    seg = mat.local_seg + (jnp.arange(T, dtype=jnp.int32) * R)[:, None]
    rows = mat.seg_row.reshape(-1)
    if x.ndim == 2:
        contrib = vals[..., None] * x[mat.col_idx]         # [T, S, B]
        partial = jax.ops.segment_sum(
            contrib.reshape(T * S, -1), seg.reshape(-1), num_segments=T * R
        )
        out = jnp.zeros((m + 1, x.shape[1]), partial.dtype)
        return out.at[rows].add(partial)[:m]
    contrib = vals * x[mat.col_idx]                        # [T, S]
    partial = jax.ops.segment_sum(
        contrib.reshape(-1), seg.reshape(-1), num_segments=T * R
    )
    out = jnp.zeros((m + 1,), partial.dtype)
    return out.at[rows].add(partial)[:m]


def _dia_plane(mat: DIAHybridMatrix, x: jax.Array) -> jax.Array:
    """DIA-plane partial y, mirroring the Pallas kernel's float ops exactly.

    x is extended with the same ``lead`` zero margin the kernel wrapper
    builds; per-slot f32 products are reduced over the diagonal axis with
    the same ``jnp.sum`` the kernel uses — so kernel == oracle holds bitwise
    (off-matrix reads pair a zero slot value with a zero margin read on both
    sides, and the axis reduction lowers to the same pairwise tree eager and
    jitted, unlike an FMA chain or a ones-vector dot).
    """
    m, n = mat.shape
    offs = mat.offsets
    if not offs:
        return jnp.zeros((m,) + x.shape[1:], jnp.float32).astype(x.dtype)
    lead = max(0, -min(offs))
    hi = max(max(offs), 0)
    L = lead + max(m + hi, n)
    pad = [(lead, L - lead - n)] + [(0, 0)] * (x.ndim - 1)
    x_ext = jnp.pad(x, pad).astype(jnp.float32)
    xs = jnp.stack([x_ext[off + lead : off + lead + m] for off in offs])
    vals = mat.diag_vals.astype(jnp.float32)
    if x.ndim == 2:
        contrib = vals[..., None] * xs                     # [n_diag, m, B]
    else:
        contrib = vals * xs                                # [n_diag, m]
    return jnp.sum(contrib, axis=0).astype(x.dtype)


@annotated("repro.oracle.spmv_diahybrid")
def spmv_diahybrid(mat: DIAHybridMatrix, x: jax.Array) -> jax.Array:
    """Partially-diagonal hybrid oracle: shifted-slice DIA contraction plus
    the CSR remainder through the canonical CSR oracle — the same two-part
    sum the kernel wrapper performs, in the same order.  ``x`` may carry a
    trailing batch dimension ([n, B] → [m, B])."""
    y = _dia_plane(mat, x)
    if mat.remainder.nnz:
        rem = (
            spmm_csr(mat.remainder, x) if x.ndim == 2
            else spmv_csr(mat.remainder, x)
        )
        y = y + rem.astype(y.dtype)
    return y


@annotated("repro.oracle.spmm_csr")
def spmm_csr(mat: CSRMatrix, X: jax.Array) -> jax.Array:
    """SpMM oracle (multi-vector SpMV), used by the CG block solver."""
    rows = jnp.repeat(
        jnp.arange(mat.m, dtype=jnp.int32),
        mat.row_lengths(),
        total_repeat_length=mat.nnz,
    )
    contrib = mat.vals[:, None] * X[mat.col_idx]
    return jax.ops.segment_sum(contrib, rows, num_segments=mat.m)


def spmv_csr5_like(mat, x: jax.Array) -> jax.Array:
    """CSR5-like SpMV: rows reconstructed from the bit-flag prefix sum
    (the format's defining trick), then a segmented sum."""
    compact = jnp.clip(
        jnp.cumsum(mat.row_flag.astype(jnp.int32)) - 1,
        0, mat.nonempty_rows.shape[0] - 1,
    )
    rows = mat.nonempty_rows[compact]
    contrib = mat.vals * x[mat.col_idx]
    # padded slots carry val 0 → inert
    return jax.ops.segment_sum(contrib, rows, num_segments=mat.shape[0])
