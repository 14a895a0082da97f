"""CSR-k containers: the paper's hierarchical format plus its TPU tile view.

CSR-k (Lane & Booth 2022) stores a sparse matrix as plain CSR plus k-1 extra
pointer arrays that group contiguous rows into super-rows (``sr_ptr``) and
contiguous super-rows into super-super-rows (``ssr_ptr``).  The base CSR arrays
are untouched, so any CSR consumer can read a CSR-k matrix directly — that is
the paper's heterogeneity argument and we preserve it here: ``CSRkMatrix.csr``
is a zero-copy view.

The TPU execution path additionally materialises a *padded tile view*
(:class:`CSRkTiles`) in which every super-super-row owns a fixed number of rows
and a fixed number of nnz slots so a Pallas ``BlockSpec`` can move one SSR per
grid step.  The tile view is derived, never stored as the source of truth.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.csr import CSRMatrix
from repro.sparse.quant import INT8_GROUP, quantize_int8_grouped

Array = Any

_INT = jnp.int32

#: Bytes per stored value, by tile-view value dtype.  Mirrors the accounting
#: in ``repro.core.tuner.tile_bytes_model`` (value + 4B col + 4B row indices).
VALUE_BYTES = {"f32": 4, "bf16": 2, "int8": 1}


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CSRkMatrix:
    """CSR-k: CSR + super-row / super-super-row pointer arrays (paper Fig. 2).

    ``k == 2`` → only ``sr_ptr`` is meaningful (``ssr_ptr`` groups all SRs into
    one trivial SSR); ``k == 3`` → both levels are real. This mirrors the
    paper's CSR-2-on-CPU / CSR-3-on-GPU split.
    """

    row_ptr: Array   # [m+1]   cumulative nnz per row
    col_idx: Array   # [nnz]
    vals: Array      # [nnz]
    sr_ptr: Array    # [num_sr+1]  cumulative rows per super-row
    ssr_ptr: Array   # [num_ssr+1] cumulative super-rows per super-super-row
    shape: Tuple[int, int]
    k: int = 3

    def tree_flatten(self):
        return (
            (self.row_ptr, self.col_idx, self.vals, self.sr_ptr, self.ssr_ptr),
            (self.shape, self.k),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, shape=aux[0], k=aux[1])

    # -- the heterogeneity property: CSR view is zero-copy -------------------
    @property
    def csr(self) -> CSRMatrix:
        return CSRMatrix(self.row_ptr, self.col_idx, self.vals, self.shape)

    @property
    def nnz(self) -> int:
        return int(self.vals.shape[0])

    @property
    def m(self) -> int:
        return self.shape[0]

    @property
    def n(self) -> int:
        return self.shape[1]

    @property
    def num_sr(self) -> int:
        return int(self.sr_ptr.shape[0]) - 1

    @property
    def num_ssr(self) -> int:
        return int(self.ssr_ptr.shape[0]) - 1

    @property
    def rdensity(self) -> float:
        return self.nnz / max(self.m, 1)

    def todense(self) -> Array:
        return self.csr.todense()

    def overhead_bytes(self) -> int:
        """Extra bytes over plain CSR (the paper's Fig. 12 quantity)."""
        extra = self.sr_ptr.size
        if self.k >= 3:
            extra += self.ssr_ptr.size
        return int(extra) * 4

    def overhead_fraction(self) -> float:
        base = (2 * self.nnz + self.m + 1) * 4
        return self.overhead_bytes() / base

    def validate(self) -> None:
        sr = np.asarray(self.sr_ptr)
        ssr = np.asarray(self.ssr_ptr)
        rp = np.asarray(self.row_ptr)
        assert sr[0] == 0 and sr[-1] == self.m, "sr_ptr must cover all rows"
        assert ssr[0] == 0 and ssr[-1] == self.num_sr, "ssr_ptr must cover all SRs"
        assert np.all(np.diff(sr) > 0), "super-rows must be non-empty"
        assert np.all(np.diff(ssr) > 0), "super-super-rows must be non-empty"
        assert rp[-1] == self.nnz


def build_csrk(
    csr: CSRMatrix,
    srs: int,
    ssrs: int | None = None,
    k: int = 3,
) -> CSRkMatrix:
    """Group rows into super-rows of ~``srs`` rows and SRs into SSRs of ~``ssrs``
    super-rows.  Sizes follow the tuner; groups are contiguous (paper Fig. 2).
    """
    m = csr.m
    srs = max(int(srs), 1)
    num_sr = (m + srs - 1) // srs
    sr_ptr = np.minimum(np.arange(num_sr + 1, dtype=np.int64) * srs, m).astype(np.int32)
    if k >= 3:
        ssrs = max(int(ssrs or 1), 1)
        num_ssr = (num_sr + ssrs - 1) // ssrs
        ssr_ptr = np.minimum(
            np.arange(num_ssr + 1, dtype=np.int64) * ssrs, num_sr
        ).astype(np.int32)
    else:
        ssr_ptr = np.asarray([0, num_sr], np.int32)
    return CSRkMatrix(
        csr.row_ptr,
        csr.col_idx,
        csr.vals,
        jnp.asarray(sr_ptr),
        jnp.asarray(ssr_ptr),
        csr.shape,
        k=k,
    )


# ---------------------------------------------------------------------------
# CSR-k padded tile view for the TPU kernel
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CSRkTiles:
    """Padded per-SSR tile view of a CSR-k matrix (TPU adaptation, DESIGN §2).

    Each SSR (one Pallas grid step) owns:
      * ``rows_per_tile`` contiguous output rows (uniform; last tile padded),
      * ``slots`` nnz slots (padded to the max SSR nnz, rounded up to 128),
      * a contiguous x-window of ``2·window`` columns starting at block
        ``win_block`` (element offset ``win_block · window``).

    The window is addressed as *two adjacent blocks* of width ``window`` so a
    ``BlockSpec`` index map (which works in block units) can place it: the
    SSR's minimum column ``lo`` gives ``win_block = lo // window`` and, since
    Band-k bounds the SSR column span to ≤ ``window``, every in-band column
    satisfies ``0 ≤ col − win_block·window < 2·window``.

    ``local_col`` indexes within the 2-block window; ``local_row`` within the
    tile's rows. Padding slots carry ``vals == 0`` and index 0 so they are
    numerically inert. Entries outside the window are diverted to a COO
    remainder (empty after Band-k on all suites).

    ``col_blocks`` lists, per tile, the 128-column blocks of its 2-block
    window that its real slots read: ``[T, 1 + K]`` int32, a count, then
    that many block indices in ascending order (zeros past the count; K is
    the most any tile needs).  The kernel's one-hot gather visits only the
    chunks holding a listed block, so its work follows the tile's columns,
    not the window width.  Real slots are the first ``tile_nnz`` — an
    explicitly stored zero counts, so ``0·x`` still meets its x; padding
    slots do not.  A zero row (a padding tile) visits nothing.

    ``value_dtype`` selects how ``vals`` is stored: ``"f32"`` (as built),
    ``"bf16"`` (half the value bytes, exact codes for the suite's small-int
    stencil weights), or ``"int8"`` with per-group symmetric scales in
    ``val_scale`` (one f32 scale per :data:`INT8_GROUP` slots — the GPTQ-style
    grouped-scale idiom from :mod:`repro.sparse.quant`).  Kernels and
    oracles dequantize on load and accumulate in f32 either way; the COO
    remainder always stays f32.  ``tile_nnz`` records each tile's real
    (in-window) entry count so :func:`bucket_tiles` can compact slots without
    mistaking explicitly-stored zeros for padding.
    """

    vals: Array        # [T, slots] f32 | bf16 | int8 (see value_dtype)
    local_col: Array   # [T, slots] int32, in [0, 2*window)
    local_row: Array   # [T, slots] int32, in [0, rows_per_tile)
    win_block: Array   # [T] int32, x-window block index (elements = blk*window)
    # COO remainder for out-of-window entries
    rem_row: Array     # [R] int32
    rem_col: Array     # [R] int32
    rem_val: Array     # [R]
    shape: Tuple[int, int]
    rows_per_tile: int
    window: int
    val_scale: Any = None      # [T, slots/INT8_GROUP] f32, int8 path only
    tile_nnz: Any = None       # [T] int32 real in-window entries per tile
    value_dtype: str = "f32"
    col_blocks: Any = None     # [T, 1+K] int32 count + window blocks read

    def tree_flatten(self):
        return (
            (self.vals, self.local_col, self.local_row, self.win_block,
             self.rem_row, self.rem_col, self.rem_val, self.val_scale,
             self.tile_nnz, self.col_blocks),
            (self.shape, self.rows_per_tile, self.window, self.value_dtype),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children[:7], shape=aux[0], rows_per_tile=aux[1],
                   window=aux[2], val_scale=children[7], tile_nnz=children[8],
                   value_dtype=aux[3], col_blocks=children[9])

    @property
    def num_tiles(self) -> int:
        return int(self.vals.shape[0])

    @property
    def slots(self) -> int:
        return int(self.vals.shape[1])

    @property
    def remainder_nnz(self) -> int:
        return int(self.rem_val.shape[0])

    def padding_overhead(self) -> float:
        """Padded-slot fraction: the tile view's memory-waste metric."""
        real = float(np.count_nonzero(np.asarray(self.vals))) + self.remainder_nnz
        return (self.num_tiles * self.slots + self.remainder_nnz - real) / max(real, 1.0)

    def modeled_bytes(self) -> int:
        """Modeled per-SpMV HBM traffic of the monolithic kernel launch.

        Same accounting as ``repro.core.tuner.tile_bytes_model``: every tile
        moves ``slots`` value/col/row slots plus the 2-block x-window and its
        y rows; the int8 path adds one f32 scale per :data:`INT8_GROUP` slots.
        """
        vb = VALUE_BYTES[self.value_dtype]
        per_tile = self.slots * (vb + 8) + 2 * self.window * 4 + self.rows_per_tile * 4
        if self.val_scale is not None:
            per_tile += (self.slots // INT8_GROUP) * 4
        return self.num_tiles * per_tile + self.remainder_nnz * 12

    def chunks_visited(self, chunk: int) -> np.ndarray:
        """One-hot chunks of width ``chunk`` each tile's gather visits
        (host-side, ``[num_tiles]``): the distinct chunks holding a block
        of its ``col_blocks`` row."""
        cb = np.asarray(self.col_blocks)
        count, q = cb[:, 0], cb[:, 1:] // (chunk // 128)
        listed = np.arange(q.shape[1]) < count[:, None]
        first = np.ones_like(listed)
        first[:, 1:] = q[:, 1:] != q[:, :-1]
        return (listed & first).sum(axis=1)

    def col_reach(self):
        """Per-tile real column reach ``(lo, hi)`` (host-side, numpy).

        Only slots with ``vals != 0`` constrain the reach — padding (and
        int8-quantized-to-zero) slots multiply by zero and are inert, the
        same rule the distributed layer's halo measurement has always used.
        Empty tiles report ``lo > hi`` (``lo = INT32_MAX``, ``hi = -1``).

        Returns:
          ``(lo, hi)``: two ``[num_tiles]`` int64 arrays of absolute column
          indices, feeding
          :func:`repro.sparse.stats.classify_tile_reach`.
        """
        v = np.asarray(self.vals)
        lc = np.asarray(self.local_col).astype(np.int64)
        wb = np.asarray(self.win_block).astype(np.int64)
        cols = wb[:, None] * self.window + lc              # [T, S] absolute
        mask = v != 0
        lo = np.where(mask, cols, np.iinfo(np.int32).max).min(
            axis=1, initial=np.iinfo(np.int32).max
        )
        hi = np.where(mask, cols, -1).max(axis=1, initial=-1)
        return lo, hi


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _pack_values(tvals: np.ndarray, value_dtype: str):
    """Convert the freshly built f32 tile values to ``value_dtype``.

    Returns ``(vals_device, val_scale_device_or_None)``.  bf16 is a plain
    cast; int8 uses the grouped-scale idiom from :mod:`repro.sparse.quant`
    (one f32 scale per :data:`INT8_GROUP` slots along the slot axis).
    """
    if value_dtype == "f32":
        return jnp.asarray(tvals), None
    if value_dtype == "bf16":
        return jnp.asarray(tvals).astype(jnp.bfloat16), None
    if value_dtype == "int8":
        q, scales = quantize_int8_grouped(tvals, group=INT8_GROUP)
        return jnp.asarray(q), jnp.asarray(scales)
    raise ValueError(
        f"unknown value_dtype {value_dtype!r} (expected f32|bf16|int8)"
    )


def col_block_table(local_col: np.ndarray, tile_nnz: np.ndarray,
                    window: int) -> np.ndarray:
    """The ``col_blocks`` table of a tile view (host-side, numpy).

    Marks, per tile, the 128-column blocks of its ``2·window`` x-window that
    hold the column of one of its first ``tile_nnz`` slots, and lists them
    (see :class:`CSRkTiles`).
    """
    lc = np.asarray(local_col)
    T, S = lc.shape
    nb = 2 * window // 128
    t, s = np.nonzero(np.arange(S) < np.asarray(tile_nnz)[:, None])
    marked = np.zeros((T, nb), bool)
    marked[t, lc[t, s] // 128] = True
    count = marked.sum(axis=1)
    K = max(int(count.max(initial=0)), 1)
    listed = np.argsort(~marked, axis=1, kind="stable")[:, :K]
    listed = np.where(np.arange(K) < count[:, None], listed, 0)
    return np.concatenate([count[:, None], listed], axis=1).astype(np.int32)


def tiles_from_csrk(
    mat: CSRkMatrix, window: int | None = None, value_dtype: str = "f32"
) -> CSRkTiles:
    """Materialise the padded per-SSR tile view (host-side setup, numpy).

    ``window`` is the x-window *block* width in columns (rounded up to 128).
    If None it is chosen as the max SSR column span rounded up — i.e. Band-k
    decides it (DESIGN §2: banding makes the window contiguous and small).
    ``value_dtype`` ∈ {"f32", "bf16", "int8"} compresses the value stream
    (see :class:`CSRkTiles`); indices and the COO remainder stay as-is.
    """
    rp = np.asarray(mat.row_ptr)
    ci = np.asarray(mat.col_idx)
    vl = np.asarray(mat.vals)
    sr = np.asarray(mat.sr_ptr)
    ssr = np.asarray(mat.ssr_ptr)
    m, n = mat.shape

    # rows covered by each SSR. The kernel's y BlockSpec needs a uniform row
    # stride per grid step, so SSRs must be uniform (build_csrk guarantees it;
    # Band-k hierarchies are regularised before reaching the kernel path).
    ssr_row_start = sr[ssr[:-1]]
    ssr_row_end = sr[ssr[1:]]
    T = len(ssr_row_start)
    rows_per_tile = int((ssr_row_end - ssr_row_start).max(initial=1))
    if not np.all(ssr_row_start == np.arange(T) * rows_per_tile):
        raise ValueError(
            "tiles_from_csrk requires uniform SSR row counts "
            "(use build_csrk / regularised hierarchy for the TPU kernel path)"
        )

    # column span per SSR → window block size (Band-k bounds this)
    spans = []
    for t in range(T):
        s, e = rp[ssr_row_start[t]], rp[ssr_row_end[t]]
        if e > s:
            spans.append(int(ci[s:e].max()) - int(ci[s:e].min()) + 1)
        else:
            spans.append(1)
    if window is None:
        window = _round_up(max(spans), 128)
    else:
        window = _round_up(int(window), 128)

    max_nnz = 0
    for t in range(T):
        max_nnz = max(max_nnz, int(rp[ssr_row_end[t]] - rp[ssr_row_start[t]]))
    slots = _round_up(max(max_nnz, 1), 128)

    tvals = np.zeros((T, slots), vl.dtype)
    tlc = np.zeros((T, slots), np.int32)
    tlr = np.zeros((T, slots), np.int32)
    twin = np.zeros((T,), np.int32)
    tnnz = np.zeros((T,), np.int32)
    rem_r, rem_c, rem_v = [], [], []

    for t in range(T):
        r0, r1 = int(ssr_row_start[t]), int(ssr_row_end[t])
        s, e = int(rp[r0]), int(rp[r1])
        if e == s:
            continue
        cols = ci[s:e]
        vals = vl[s:e]
        rows = np.repeat(np.arange(r0, r1), rp[r0 + 1 : r1 + 1] - rp[r0:r1])
        blk = int(cols.min()) // window
        twin[t] = blk
        start = blk * window
        inw = (cols >= start) & (cols < start + 2 * window)
        k = int(inw.sum())
        tvals[t, :k] = vals[inw]
        tlc[t, :k] = cols[inw] - start
        tlr[t, :k] = rows[inw] - r0
        tnnz[t] = k
        if k < len(cols):
            out = ~inw
            rem_r.append(rows[out])
            rem_c.append(cols[out])
            rem_v.append(vals[out])

    if rem_r:
        rem_r = np.concatenate(rem_r)
        rem_c = np.concatenate(rem_c)
        rem_v = np.concatenate(rem_v)
    else:
        rem_r = np.zeros((0,), np.int32)
        rem_c = np.zeros((0,), np.int32)
        rem_v = np.zeros((0,), vl.dtype)

    dvals, dscale = _pack_values(tvals, value_dtype)
    return CSRkTiles(
        dvals,
        jnp.asarray(tlc),
        jnp.asarray(tlr),
        jnp.asarray(twin, _INT),
        jnp.asarray(rem_r, _INT),
        jnp.asarray(rem_c, _INT),
        jnp.asarray(rem_v),
        (m, n),
        rows_per_tile,
        window,
        val_scale=dscale,
        tile_nnz=jnp.asarray(tnnz, _INT),
        value_dtype=value_dtype,
        col_blocks=jnp.asarray(col_block_table(tlc, tnnz, window)),
    )


# ---------------------------------------------------------------------------
# slot-bucketed tile view (SELL-C-σ-style per-bucket compaction for CSR-k)
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CSRkTileBuckets:
    """Slot-compacted CSR-k tile view: tiles grouped by rounded-up nnz count.

    The monolithic :class:`CSRkTiles` pads every tile to the single worst
    tile's slot count, so the kernel's HBM traffic scales with ``T · max_t
    nnz_t`` instead of ``Σ_t nnz_t``.  Bucketing applies the SELL-C-σ trick
    (Kreutzer et al., arXiv:1307.6209) at tile granularity: tiles whose nnz
    rounds up to the same 128-multiple (the same rounding
    ``repro.core.tuner.tile_bytes_model`` prices, so the tuner and this
    builder agree on bytes) share one bucket, stored as its own ``[T_b, S_b]``
    array set and launched as its own Pallas grid.

    Each bucket is a self-consistent :class:`CSRkTiles` over its *own
    compacted row space* (bucket tile ``i`` owns local rows ``[i·R, (i+1)·R)``
    and ``shape[0] == T_b · R``); ``tile_ids[b][i]`` maps bucket tile ``i``
    back to its global tile, so callers scatter bucket outputs into global
    rows ``tile_ids[b][i] · R``.  Because compaction only drops trailing
    all-padding slots, every real slot keeps its position and the per-bucket
    launches are bit-for-bit identical to the monolithic kernel (pinned by
    tests/test_tile_buckets.py).  The COO remainder is held once, here.
    """

    buckets: Tuple[CSRkTiles, ...]
    tile_ids: Tuple[Array, ...]   # per bucket: [T_b] int32 global tile ids
    rem_row: Array                # [R] int32
    rem_col: Array                # [R] int32
    rem_val: Array                # [R]
    shape: Tuple[int, int]
    rows_per_tile: int
    window: int
    num_tiles: int
    value_dtype: str = "f32"

    def tree_flatten(self):
        return (
            (self.buckets, self.tile_ids, self.rem_row, self.rem_col,
             self.rem_val),
            (self.shape, self.rows_per_tile, self.window, self.num_tiles,
             self.value_dtype),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, shape=aux[0], rows_per_tile=aux[1],
                   window=aux[2], num_tiles=aux[3], value_dtype=aux[4])

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    @property
    def remainder_nnz(self) -> int:
        return int(self.rem_val.shape[0])

    def bucket_slots(self) -> Tuple[int, ...]:
        return tuple(b.slots for b in self.buckets)

    def padding_overhead(self) -> float:
        """Padded-slot fraction across all buckets (cf. CSRkTiles)."""
        real = self.remainder_nnz
        total = self.remainder_nnz
        for b in self.buckets:
            real += int(np.count_nonzero(np.asarray(b.vals)))
            total += b.num_tiles * b.slots
        return (total - real) / max(float(real), 1.0)

    def modeled_bytes(self) -> int:
        """Modeled per-SpMV HBM traffic, summed over the per-bucket launches.

        ``Σ_b T_b · (S_b·(value+8) + 2·window·4 + rows·4)`` — same per-tile
        accounting as :meth:`CSRkTiles.modeled_bytes`, but each tile is priced
        at its bucket's compacted slot count instead of the global worst.
        """
        return sum(b.modeled_bytes() for b in self.buckets) + self.remainder_nnz * 12


def bucket_tiles(tiles: CSRkTiles) -> CSRkTileBuckets:
    """Regroup a monolithic tile view into slot buckets (host-side, numpy).

    Tiles are keyed by ``round_up(max(tile_nnz, 1), 128)`` and each bucket's
    arrays are the original rows sliced to the bucket's slot count — real
    entries are packed at the front of every tile, so slicing drops only
    trailing padding and the kernel output is unchanged bit-for-bit.
    """
    v = np.asarray(tiles.vals)
    lc = np.asarray(tiles.local_col)
    lr = np.asarray(tiles.local_row)
    wb = np.asarray(tiles.win_block)
    sc = None if tiles.val_scale is None else np.asarray(tiles.val_scale)
    cb = np.asarray(tiles.col_blocks)
    if tiles.tile_nnz is not None:
        nnz_t = np.asarray(tiles.tile_nnz)
    else:  # hand-built views: padding is 0-valued, real zeros are not packed
        nnz_t = (v != 0).sum(axis=1)
    slots_t = np.minimum(((np.maximum(nnz_t, 1) + 127) // 128) * 128, tiles.slots)

    buckets, ids = [], []
    for S_b in sorted(set(int(s) for s in slots_t)):
        sel = np.flatnonzero(slots_t == S_b)
        scale_b = None
        if sc is not None:
            scale_b = jnp.asarray(sc[sel, : S_b // INT8_GROUP])
        buckets.append(CSRkTiles(
            jnp.asarray(v[sel, :S_b]),
            jnp.asarray(lc[sel, :S_b]),
            jnp.asarray(lr[sel, :S_b]),
            jnp.asarray(wb[sel], _INT),
            jnp.zeros((0,), _INT),
            jnp.zeros((0,), _INT),
            jnp.zeros((0,), np.asarray(tiles.rem_val).dtype),
            (len(sel) * tiles.rows_per_tile, tiles.shape[1]),
            tiles.rows_per_tile,
            tiles.window,
            val_scale=scale_b,
            tile_nnz=jnp.asarray(nnz_t[sel], _INT),
            value_dtype=tiles.value_dtype,
            col_blocks=jnp.asarray(cb[sel]),
        ))
        ids.append(jnp.asarray(sel, _INT))
    return CSRkTileBuckets(
        tuple(buckets),
        tuple(ids),
        tiles.rem_row,
        tiles.rem_col,
        tiles.rem_val,
        tiles.shape,
        tiles.rows_per_tile,
        tiles.window,
        tiles.num_tiles,
        value_dtype=tiles.value_dtype,
    )
