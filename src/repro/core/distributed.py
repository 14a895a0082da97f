"""Distributed SpMV: row-partitioned A across the mesh (shard_map).

The paper targets a single device; this is the framework layer that makes
CSR-k a *cluster* citizen.  Two levels live here:

1. The low-level :class:`ShardedCSR` + ``dist_spmv_*`` functions: a plain
   row-partitioned CSR executed with the pure-jnp oracle inside ``shard_map``
   (the off-TPU fallback path, and the historical entry point).  Both are
   thin shims over the same plan executor the prepared path uses.

2. The prepared-operator integration: :func:`shard_prepared` wraps a
   single-device :class:`~repro.core.spmv.PreparedSpMV` into a
   :class:`ShardedPreparedSpMV` that partitions the operator's *kernel tile
   view* across the mesh and runs the actual Pallas CSR-k / SELL-C-σ kernels
   inside ``shard_map``.  ``prepare(A, mesh=...)`` is the public spelling.

Execution is organised around a :class:`ShardPlan` built once at
``shard_prepared`` time.  The plan records, per shard, which kernel tiles are
**interior** (every real column they read lies inside the shard's own x
slice) and which are **boundary** (they touch a neighbour's rows), plus the
halo send/recv schedule — only the edges a boundary tile actually needs.
The executor is phase-structured:

  1. put the halo ``ppermute``\\ s on the wire (no data dependence on any
     compute, so an async-collectives backend can overlap them),
  2. run the interior tiles against the local x slice while the exchange is
     in flight,
  3. run the boundary tiles against the received halo window and scatter
     both launches' rows back to their home tiles.

The replicated and all-gather strategies are expressed as *degenerate* plans
(no tile split, no edges) through the same executor, so all three x
strategies share one code path.  x is distributed per strategy:

  * **replicated** (small n — iterative-solver regime; no collective),
  * **all-gather-x**: row-sharded with a pre-SpMV all-gather that XLA can
    overlap with the leading tiles' compute (O(n) collective), or
  * **halo-exchange-x**: because Band-k bounds each shard's column span,
    shard d only needs x over its band window — its own slice plus ≤H columns
    from each neighbour, an O(band) collective-permute instead of an O(n)
    all-gather.  This is the beyond-paper distributed optimisation.

:func:`select_x_strategy` picks between the three in O(1) from
:class:`~repro.sparse.stats.MatrixStats` (band width vs n), mirroring the
registry's constant-time format selection.

Tile partitioning (not raw row partitioning) is what makes the sharded
operator *bit-for-bit* identical to the single-device one: every kernel
instance sees exactly the same tile contents, static block shapes and slot
ordering as the global launch, so per-row floating-point summation order is
unchanged.  The interior/boundary split preserves this — each tile still runs
the unmodified kernel on its unmodified contents, and tile row ranges are
disjoint, so scattering the two launches back together reproduces the
monolithic launch exactly.  ``tests/test_sharded_prepare.py`` and
``tests/test_shard_plan.py`` pin this for both backends, [n] and [n, B]
inputs, all three x strategies, and overlapped-vs-blocking execution.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.formats import CSRMatrix
from repro.kernels.ops import _pad_rows, combine_tile_rows
from repro.obs import get_registry
from repro.sparse.csrk import _round_up
from repro.sparse.stats import MatrixStats, classify_tile_reach, compute_shard_stats

_LANE = 128


@dataclasses.dataclass(frozen=True)
class ShardedCSR:
    """Row-partitioned CSR: per-shard padded arrays stacked on axis 0."""

    row_ptr: jax.Array   # [D, rows_per_shard+1]
    col_idx: jax.Array   # [D, max_nnz]
    vals: jax.Array      # [D, max_nnz]
    shape: Tuple[int, int]
    rows_per_shard: int
    halo: int            # max distance a column reaches outside the shard's rows


def shard_csr(A: CSRMatrix, num_shards: int) -> ShardedCSR:
    """Partition rows contiguously into ``num_shards`` padded shards.

    Args:
      A: the (already reordered) global CSR matrix.
      num_shards: number of contiguous row blocks (mesh axis size).

    Returns:
      A :class:`ShardedCSR` whose stacked arrays have leading dimension
      ``num_shards``; padding nnz slots carry ``vals == 0`` so they are inert.
    """
    m, n = A.shape
    rp = np.asarray(A.row_ptr)
    ci = np.asarray(A.col_idx)
    vl = np.asarray(A.vals)
    rows_per_shard = -(-m // num_shards)
    max_nnz = 0
    for d in range(num_shards):
        r0, r1 = d * rows_per_shard, min((d + 1) * rows_per_shard, m)
        max_nnz = max(max_nnz, int(rp[r1] - rp[r0]))
    max_nnz = max(_round_up(max_nnz, _LANE), _LANE)

    s_rp = np.zeros((num_shards, rows_per_shard + 1), np.int32)
    s_ci = np.zeros((num_shards, max_nnz), np.int32)
    s_vl = np.zeros((num_shards, max_nnz), vl.dtype)
    halo = 0
    for d in range(num_shards):
        r0, r1 = d * rows_per_shard, min((d + 1) * rows_per_shard, m)
        base = rp[r0]
        local_rp = rp[r0 : r1 + 1] - base
        s_rp[d, : r1 - r0 + 1] = local_rp
        s_rp[d, r1 - r0 + 1 :] = local_rp[-1]
        k = int(rp[r1] - base)
        s_ci[d, :k] = ci[base : base + k]
        s_vl[d, :k] = vl[base : base + k]
        if k:
            lo, hi = int(s_ci[d, :k].min()), int(s_ci[d, :k].max())
            halo = max(halo, r0 - lo, hi - (r1 - 1))
    return ShardedCSR(
        jnp.asarray(s_rp), jnp.asarray(s_ci), jnp.asarray(s_vl),
        (m, n), rows_per_shard, max(halo, 0),
    )


def _local_spmv(row_ptr, col_idx, vals, x_full, col_offset=0):
    """Segmented SpMV on one padded shard; padding rows produce 0.

    ``x_full`` may be a vector ([L]) or a multi-vector block ([L, B]); the
    trailing batch dimension rides through the segment-sum unchanged.
    """
    rows_per_shard = row_ptr.shape[0] - 1
    nnz = col_idx.shape[0]
    lengths = row_ptr[1:] - row_ptr[:-1]
    rows = jnp.repeat(
        jnp.arange(rows_per_shard, dtype=jnp.int32), lengths, total_repeat_length=nnz
    )
    # padded slots repeat the last row; their vals are 0 so they are inert
    gathered = jnp.take(x_full, col_idx - col_offset, axis=0, mode="clip")
    if x_full.ndim == 2:
        contrib = vals[:, None] * gathered
    else:
        contrib = vals * gathered
    return jax.ops.segment_sum(contrib, rows, num_segments=rows_per_shard)


# ---------------------------------------------------------------------------
# the staged execution plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Static schedule for one sharded SpMV operator, built at prepare time.

    The plan separates *what was decided* from *how it executes*: the
    resolved x strategy, the tile partition geometry, the interior/boundary
    tile split and the halo edge schedule all live here, and one executor
    (:func:`_build_plan_call` / :func:`_csr_plan_shard_map`) interprets them.
    Replicated and all-gather strategies are degenerate plans — no tile
    split, no edges — so all three strategies flow through the same code.

    Attributes:
      strategy: resolved x distribution ("replicated" | "allgather" | "halo").
      num_shards / rows_per_shard: partition geometry (tile-granular rows).
      halo: exchanged rows per neighbour edge (0 unless strategy is "halo").
      tiles_per_shard / rows_per_tile: kernel tile geometry (0 for the CSR
        oracle fallback, which has no tile view).
      overlap: when True the executor runs phase-structured — halo permutes
        first, interior tiles while the exchange is in flight, boundary tiles
        against the received window.  False means one monolithic launch after
        x distribution (the "blocking" schedule).
      interior_ids / boundary_ids: per-shard int32 arrays of *local* tile ids
        (populated whenever the tile reach was classified, i.e. tile backends
        under the halo strategy, independent of ``overlap``).
      interior_fraction: fraction of non-empty tiles that are interior — the
        O(1) signal for whether overlapping the exchange can pay.
      left_edges / right_edges: ``(src, dst)`` ppermute pairs delivering each
        receiver's left resp. right halo.  Need-based for tile backends: an
        edge exists only if the receiver has a boundary tile reaching that
        side, so shards with purely interior reach exchange nothing.
    """

    strategy: str
    num_shards: int
    rows_per_shard: int
    halo: int = 0
    tiles_per_shard: int = 0
    rows_per_tile: int = 0
    overlap: bool = False
    interior_fraction: float = 1.0
    interior_ids: Tuple = ()
    boundary_ids: Tuple = ()
    left_edges: Tuple[Tuple[int, int], ...] = ()
    right_edges: Tuple[Tuple[int, int], ...] = ()

    @property
    def is_degenerate(self) -> bool:
        """True when no halo schedule exists (replicated / allgather plans)."""
        return self.strategy != "halo"

    @property
    def num_interior(self) -> int:
        """Max interior tiles on any shard (the interior launch width)."""
        return max((len(i) for i in self.interior_ids), default=0)

    @property
    def num_boundary(self) -> int:
        """Max boundary tiles on any shard (the boundary launch width)."""
        return max((len(b) for b in self.boundary_ids), default=0)

    def collective_bytes(self, B: int = 1, itemsize: int = 4) -> int:
        """Modeled bytes moved by the x collective per SpMV/SpMM call.

        halo: ``halo`` rows per *scheduled edge* — since edges are need-based,
        only sides that boundary tiles actually read are counted (an interior-
        only shard contributes nothing).  allgather: every shard receives the
        other D−1 shards' rows.  replicated: 0 (x is already everywhere).
        """
        per_row = itemsize * max(B, 1)
        if self.strategy == "halo":
            n_edges = len(self.left_edges) + len(self.right_edges)
            return self.halo * n_edges * per_row
        if self.strategy == "allgather":
            D, R = self.num_shards, self.rows_per_shard
            return (D - 1) * R * D * per_row
        return 0


def _ring_edges(D: int):
    """Full bidirectional ring schedule (legacy ``dist_spmv_halo`` semantics).

    ``left``: every shard sends its tail to the right neighbour (each
    receiver gets its left halo); ``right``: mirrored.  Includes the
    wraparound pair — harmless because wraparound columns are never real.
    """
    left = tuple((i, (i + 1) % D) for i in range(D))
    right = tuple((i, (i - 1) % D) for i in range(D))
    return left, right


def _csr_plan_shard_map(plan: ShardPlan, mesh: Mesh, axis: str):
    """shard_map executor for a plan over raw CSR shards (oracle path).

    Shared by the legacy ``dist_spmv_*`` entry points and the prepared
    operator's CSR-2/CPU fallback, so the ``_local_spmv`` wiring exists
    exactly once.  Returns ``f(row_ptr, col_idx, vals, x_padded)`` operating
    on :class:`ShardedCSR`-layout stacks.
    """
    D, Rs, H = plan.num_shards, plan.rows_per_shard, plan.halo
    strategy = plan.strategy
    left_edges = [tuple(e) for e in plan.left_edges]
    right_edges = [tuple(e) for e in plan.right_edges]

    def body(rp, ci, vl, xs):
        if strategy == "halo":
            d = jax.lax.axis_index(axis)
            left = (
                jax.lax.ppermute(xs[-H:], axis, left_edges)
                if left_edges else jnp.zeros_like(xs[-H:])
            )
            right = (
                jax.lax.ppermute(xs[:H], axis, right_edges)
                if right_edges else jnp.zeros_like(xs[:H])
            )
            x_win = jnp.concatenate([left, xs, right])  # rows [d·Rs−H, d·Rs+Rs+H)
            return _local_spmv(rp[0], ci[0], vl[0], x_win, col_offset=d * Rs - H)
        if strategy == "allgather":
            x_full = jax.lax.all_gather(xs, axis, tiled=True)
        else:
            x_full = xs
        return _local_spmv(rp[0], ci[0], vl[0], x_full)

    x_spec = P() if strategy == "replicated" else P(axis)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), x_spec),
        out_specs=P(axis), check_vma=False,
    )


def dist_spmv_allgather(A: ShardedCSR, x: jax.Array, mesh: Mesh, axis: str = "data"):
    """y = A x with x row-sharded; all-gather x then local SpMV (baseline).

    ``x`` may be [n] or [n, B]; the collective moves the whole padded x
    (O(n·B) bytes) regardless of the band structure.  Thin shim over the
    degenerate all-gather :class:`ShardPlan`.
    """
    D = int(mesh.shape[axis])
    plan = ShardPlan("allgather", D, A.rows_per_shard)
    f = _csr_plan_shard_map(plan, mesh, axis)
    xpad = _pad_rows(x, A.rows_per_shard * D)
    return f(A.row_ptr, A.col_idx, A.vals, xpad)[: A.shape[0]]


def dist_spmv_halo(A: ShardedCSR, x: jax.Array, mesh: Mesh, axis: str = "data"):
    """Banded halo exchange: neighbours swap ≤halo columns (beyond-paper opt).

    Valid when ``A.halo <= A.rows_per_shard`` (guaranteed by Band-k for the
    suites we run; checked at trace time).  ``x`` may be [n] or [n, B].
    Thin shim over a full-ring halo :class:`ShardPlan` — the ring schedule
    (rather than the prepared path's need-based edges) preserves the
    historical semantics exactly.
    """
    D = int(mesh.shape[axis])
    R = A.rows_per_shard
    H = _round_up(max(A.halo, 1), _LANE)
    if H > R:
        # band too wide for single-neighbour halo — fall back
        return dist_spmv_allgather(A, x, mesh, axis)
    left, right = _ring_edges(D)
    plan = ShardPlan("halo", D, R, halo=H, left_edges=left, right_edges=right)
    f = _csr_plan_shard_map(plan, mesh, axis)
    xpad = _pad_rows(x, R * D)
    return f(A.row_ptr, A.col_idx, A.vals, xpad)[: A.shape[0]]


# ---------------------------------------------------------------------------
# prepared-operator integration: prepare(A, mesh=...) → ShardedPreparedSpMV
# ---------------------------------------------------------------------------

X_STRATEGIES = ("replicated", "allgather", "halo")

#: Below this n, replicating x everywhere is cheaper than any collective
#: bookkeeping (the iterative-solver regime the paper motivates with).
REPLICATE_N_MAX = 1 << 14

#: Minimum fraction of non-empty tiles that must be interior for the staged
#: overlap schedule to be worth its second kernel launch; below this the
#: exchange dominates anyway and the plan stays blocking.
OVERLAP_MIN_INTERIOR = 0.25


def select_x_strategy(
    stats: MatrixStats, num_shards: int, rows_per_shard: int
) -> str:
    """O(1) x-distribution choice from matrix statistics (band width vs n).

    The decision mirrors the registry's constant-time format selection: no
    SpMV is ever run, only the one-pass :class:`MatrixStats` are consulted.

    Policy (first match wins):

    * one shard → ``"replicated"`` (nothing to distribute);
    * ``round_up(bandwidth, 128) ≤ rows_per_shard`` → ``"halo"`` — Band-k
      bounds every shard's column overhang by the bandwidth, so an O(band)
      neighbour exchange suffices;
    * ``n ≤ REPLICATE_N_MAX`` → ``"replicated"`` — x is small enough that
      keeping a full copy per device beats collective latency;
    * otherwise → ``"allgather"`` — wide band *and* large n: each shard may
      read far-away columns, so gather the whole x.

    Args:
      stats: one-pass statistics of the (post-reordering) global matrix.
      num_shards: mesh axis size the rows are partitioned over.
      rows_per_shard: padded rows each shard owns.

    Returns:
      One of ``"replicated" | "allgather" | "halo"``.
    """
    if num_shards <= 1:
        return "replicated"
    if _round_up(max(int(stats.bandwidth), 1), _LANE) <= rows_per_shard:
        return "halo"
    if stats.n <= REPLICATE_N_MAX:
        return "replicated"
    return "allgather"


def estimate_interior_fraction(
    stats: MatrixStats, num_shards: int, rows_per_shard: int
) -> float:
    """O(1) estimate of the interior tile fraction from the bandwidth alone.

    After Band-k, only tiles within one bandwidth of a shard edge can be
    boundary, so at most ``2·round_up(bw, 128)`` of each shard's rows are
    boundary rows.  This is the prediction the measured
    ``ShardPlan.interior_fraction`` can be checked against without building
    any tile view — same O(1)-from-stats discipline as
    :func:`select_x_strategy`.
    """
    if num_shards <= 1:
        return 1.0
    bw = _round_up(max(int(stats.bandwidth), 1), _LANE)
    return max(0.0, 1.0 - 2.0 * bw / max(rows_per_shard, 1))


def _stack_shards(a: np.ndarray, D: int, per: int) -> jax.Array:
    """Stack a leading-dim array into [D, per, ...] with zero padding."""
    a = np.asarray(a)
    out = np.zeros((D * per,) + a.shape[1:], a.dtype)
    out[: a.shape[0]] = a
    return jnp.asarray(out.reshape((D, per) + a.shape[1:]))


def _stack_tile_subset(a, ids, D: int, Tp: int, T_sub: int) -> jax.Array:
    """Gather per-shard tile subsets of a global tile array into [D, T_sub, ...].

    ``ids`` holds each shard's *local* tile ids (shard d's tile t lives at
    global index ``d·Tp + t``).  Shards with fewer than ``T_sub`` subset
    tiles are padded with all-zero tiles, which the kernels treat as inert
    (val == 0) and whose rows go to the combine dump slot.
    """
    a = np.asarray(a)
    out = np.zeros((D, T_sub) + a.shape[1:], a.dtype)
    for d, loc in enumerate(ids):
        loc = np.asarray(loc, np.int64)
        if len(loc):
            out[d, : len(loc)] = a[d * Tp + loc]
    return jnp.asarray(out)


def _stack_subset_ids(ids, D: int, Tp: int, T_sub: int) -> jax.Array:
    """Stack local tile-id arrays into [D, T_sub]; pad slots dump to ``Tp``."""
    out = np.full((D, T_sub), Tp, np.int32)
    for d, loc in enumerate(ids):
        if len(loc):
            out[d, : len(loc)] = np.asarray(loc, np.int32)
    return jnp.asarray(out)


def _required_halo(reach, rows_per_shard: int, num_shards: int) -> int:
    """Max column overhang of any shard's *real* (val ≠ 0) entries, in rows.

    ``reach`` is a per-shard list of ``(lo, hi)`` real-column extents (or
    None for empty shards).  Padding slots multiply by 0 and are inert, so
    only real columns constrain the halo window — this is what lets the halo
    stay O(band) even though the kernels' BlockSpec windows are 128-aligned.
    """
    H = 0
    for d, r in enumerate(reach):
        if r is None:
            continue
        lo, hi = r
        r0, r1 = d * rows_per_shard, (d + 1) * rows_per_shard
        H = max(H, r0 - lo, hi + 1 - r1)
    return max(H, 0)


def _halo_edges(reach, rows_per_shard: int, num_shards: int):
    """Need-based halo schedule: one edge per side a shard actually reads.

    Shard d gets a ``(d−1, d)`` left edge only if some real column of its
    tiles lies below ``d·rows_per_shard`` (mirrored on the right).  After
    Band-k most shards need both neighbours, but block-diagonal matrices —
    or partitions where a shard's band happens to align with its slice —
    drop edges, and with them the exchanged bytes.
    """
    left, right = [], []
    for d, r in enumerate(reach):
        if r is None:
            continue
        lo, hi = r
        if lo < d * rows_per_shard and d > 0:
            left.append((d - 1, d))
        if hi >= (d + 1) * rows_per_shard and d + 1 < num_shards:
            right.append((d + 1, d))
    return tuple(left), tuple(right)


def _shard_reach(lo, hi, tiles_per_shard: int, num_shards: int):
    """Per-shard ``(lo, hi)`` real-column extents from per-tile reach."""
    lo = np.asarray(lo)
    hi = np.asarray(hi)
    T = int(lo.shape[0])
    out = []
    for d in range(num_shards):
        t0, t1 = d * tiles_per_shard, min((d + 1) * tiles_per_shard, T)
        sl, sh = lo[t0:t1], hi[t0:t1]
        real = sh >= sl
        if real.any():
            out.append((int(sl[real].min()), int(sh[real].max())))
        else:
            out.append(None)
    return out


@dataclasses.dataclass(frozen=True)
class ShardedPreparedSpMV:
    """A prepared SpMV operator partitioned across a device mesh.

    Built by :func:`shard_prepared` (or ``prepare(A, mesh=...)``).  The global
    operator's kernel tile view is split into contiguous per-shard stacks and
    executed with the *same* Pallas kernels inside ``shard_map``, so results
    are bit-for-bit identical to the single-device ``base`` operator.

    Shapes: ``__call__`` accepts ``x`` of shape [n] or [n, B] (reordered index
    space) and returns [m] resp. [m, B]; ``apply_original`` works in the
    matrix's original index space, exactly like :class:`PreparedSpMV`.

    Attributes:
      base: the single-device :class:`~repro.core.spmv.PreparedSpMV` the
        shard view was derived from (source of truth for perm/params/stats).
      mesh / axis: the mesh and the axis name rows are partitioned over.
      x_strategy_requested: what the caller asked for; the *resolved*
        strategy lives on ``plan.strategy`` (halo demotes to allgather when
        the actual column reach of a shard exceeds one neighbour's rows).
      plan: the :class:`ShardPlan` — partition geometry, interior/boundary
        tile split, halo edge schedule and the overlap decision.
      shard_stats / shard_backends: per-shard one-pass statistics and the
        registry's per-shard format decisions — recorded for introspection
        and benchmarks; execution uses the uniform ``backend`` so the SPMD
        body (and the bit-for-bit contract with ``base``) stays single-program.
      shard_arrays: the stacked per-shard kernel arrays (backend- and
        overlap-layout-dependent; keys documented in
        :func:`_build_plan_call`).
      c_csr: raw CSR shards for the oracle fallback (no tile view).
    """

    base: "object"                    # PreparedSpMV (kept untyped: no cycle)
    mesh: Mesh
    axis: str
    x_strategy_requested: str
    plan: ShardPlan
    shard_stats: Tuple[Optional[MatrixStats], ...]
    shard_backends: Tuple[str, ...]
    shard_arrays: dict = dataclasses.field(default_factory=dict)
    c_csr: Optional[ShardedCSR] = None

    def __post_init__(self):
        object.__setattr__(self, "_call_cache", {})

    # -- delegated introspection --------------------------------------------
    @property
    def backend(self) -> str:
        """The executing backend of the base operator — the global decision.

        One of ``"csrk" | "sellcs" | "segsum" | "diahybrid"``.  Only the
        first two carry a shardable tile view; the irregular-matrix backends
        decline tile partitioning and execute per-shard through the CSR-2
        oracle fallback (see :func:`shard_prepared`).
        """
        return self.base.backend

    @property
    def stats(self):
        """Global :class:`MatrixStats` (post-reordering) of the base operator."""
        return self.base.stats

    @property
    def perm(self) -> np.ndarray:
        return self.base.perm

    @property
    def params(self):
        return self.base.params

    @property
    def num_shards(self) -> int:
        return self.plan.num_shards

    @property
    def x_strategy(self) -> str:
        """The resolved x distribution ("replicated" | "allgather" | "halo")."""
        return self.plan.strategy

    @property
    def rows_per_shard(self) -> int:
        return self.plan.rows_per_shard

    @property
    def halo(self) -> int:
        return self.plan.halo

    @property
    def overlap(self) -> bool:
        """True when execution is staged (interior tiles overlap the halo)."""
        return self.plan.overlap

    @property
    def interior_fraction(self) -> float:
        return self.plan.interior_fraction

    def collective_bytes_per_call(self, B: int = 1, itemsize: int = 4) -> int:
        """Modeled bytes moved by the x collective per SpMV/SpMM call.

        Delegates to :meth:`ShardPlan.collective_bytes`: halo counts only the
        need-based edges the plan actually schedules, allgather counts the
        full O(n) gather — the O(band) vs O(n) argument in numbers.
        """
        return self.plan.collective_bytes(B, itemsize)

    # -- execution -----------------------------------------------------------
    def _executor(self):
        fn = self._call_cache.get("call")
        if fn is None:
            fn = _build_plan_call(self)
            self._call_cache["call"] = fn
        return fn

    def __call__(self, x: jax.Array) -> jax.Array:
        """Sharded SpMV / SpMM in the reordered index space ([n] or [n, B])."""
        return self._executor()(x)

    def lower(self, x: jax.Array):
        """The jitted sharded program for x's shape (``jax.stages.Lowered``),
        with the per-shard stacks as arguments rather than constants."""
        fn = self._executor()
        return fn.func.lower(*fn.args, x)

    def matmat(self, X: jax.Array) -> jax.Array:
        """Explicit multi-vector alias: Y = A X for X of shape [n, B]."""
        if X.ndim != 2:
            raise ValueError(f"matmat expects a [n, B] block, got shape {X.shape}")
        return self(X)

    def apply_original(self, x_old: jax.Array) -> jax.Array:
        """SpMV / SpMM for vectors indexed in the matrix's original ordering
        (``self(x_old)`` where the base operator's ``perm`` is the identity)."""
        if self.base._identity_perm:
            return self(x_old)
        perm, inv_perm = self.base._perm_arrays
        return self(x_old[perm])[inv_perm]


def _build_plan_call(op: ShardedPreparedSpMV):
    """Build the jitted shard_map executor for one ShardedPreparedSpMV.

    The :class:`ShardPlan` drives everything static (strategy, halo edges,
    the interior/boundary split, tile shapes); the stacked arrays and x are
    passed as arguments so jit does not bake them in as constants.  The
    returned callable accepts x of shape [n] or [n, B].

    ``shard_arrays`` layouts (all stacked [D, ...]):
      csrk blocking: ``vals/lcol/lrow/win/cblk`` (+ ``scale``);
      csrk overlap: ``i_*``/``b_*`` subset stacks + ``i_ids``/``b_ids``;
      sellcs blocking: ``vals/cols`` (+ ``scale``);
      sellcs overlap: ``i_vals/i_cols/i_ids`` and ``b_*`` counterparts.
    """
    mesh, axis, base, plan = op.mesh, op.axis, op.base, op.plan
    D, Rs, H = plan.num_shards, plan.rows_per_shard, plan.halo
    strategy = plan.strategy
    left_edges = [tuple(e) for e in plan.left_edges]
    right_edges = [tuple(e) for e in plan.right_edges]
    arrs = op.shard_arrays

    if base.backend == "csrk":
        m = base.csrk.shape[0]
    elif base.backend == "sellcs":
        m = base.sell.shape[0]
    else:
        m = op.c_csr.shape[0]

    def halo_parts(xs):
        """Phase 1: put both halo permutes on the wire.

        Issued before any compute that consumes them, with no data
        dependence on the interior launch — an async-collectives backend is
        free to overlap the exchange with phase 2.  Shards outside an edge
        list receive zeros; only val==0 padding slots ever read those rows.
        """
        left = (
            jax.lax.ppermute(xs[-H:], axis, left_edges)
            if left_edges else jnp.zeros_like(xs[-H:])
        )
        right = (
            jax.lax.ppermute(xs[:H], axis, right_edges)
            if right_edges else jnp.zeros_like(xs[:H])
        )
        return left, right

    def paste(xwin, lead, target_len):
        """Paste this shard's x window into a zero buffer of ``target_len``.

        ``xwin`` starts at absolute row ``d·Rs − lead``; the buffer is built
        ``lead`` rows long on the left so the update offset stays
        non-negative for shard 0 (dynamic_update_slice clamps, it does not
        shift).  Columns outside the window are only ever touched by val==0
        padding slots, so zeros there preserve bit-equality.
        """
        d = jax.lax.axis_index(axis)
        trail = xwin.shape[1:]
        ext_len = lead + max(target_len, D * Rs + lead)
        ext = jnp.zeros((ext_len,) + trail, xwin.dtype)
        start = (d * Rs,) + (0,) * len(trail)
        ext = jax.lax.dynamic_update_slice(ext, xwin, start)
        return ext[lead : lead + target_len]

    def distribute_x(xs, target_len):
        """Blocking x reconstruction (degenerate plans + non-overlap halo)."""
        if strategy == "replicated":
            return xs
        if strategy == "allgather":
            xfull = jax.lax.all_gather(xs, axis, tiled=True)        # [D*Rs,...]
            ext = jnp.zeros((max(target_len, D * Rs),) + xs.shape[1:], xs.dtype)
            ext = jax.lax.dynamic_update_slice(ext, xfull, (0,) * ext.ndim)
            return ext[:target_len]
        left, right = halo_parts(xs)
        return paste(jnp.concatenate([left, xs, right]), H, target_len)

    x_spec = P() if strategy == "replicated" else P(axis)

    if base.backend == "csrk" and base.tiles is not None:
        from repro.kernels.spmv_csrk import spmv_csrk_tiles_pallas

        tiles = base.tiles
        R, W = tiles.rows_per_tile, tiles.window
        nblocks = -(-tiles.shape[1] // W)
        Lp = (nblocks + 1) * W
        gather_mode, interpret = base.gather_mode, base.interpret
        chunk = base.params.gather_chunk
        has_scale = "scale" in arrs or "i_scale" in arrs

        def launch(v, lc, lr, wb, cb, xp, sc):
            return spmv_csrk_tiles_pallas(
                v, lc, lr, wb, cb, xp, sc,
                rows_per_tile=R, window=W, gather_chunk=chunk,
                gather_mode=gather_mode, interpret=interpret,
            )

        if plan.overlap:
            Tp = plan.tiles_per_shard
            names = [
                "i_vals", "i_lcol", "i_lrow", "i_win", "i_cblk", "i_ids",
                "b_vals", "b_lcol", "b_lrow", "b_win", "b_cblk", "b_ids",
            ]
            if has_scale:
                names += ["i_scale", "b_scale"]

            def body(*args):
                a = dict(zip(names, args[:-1]))
                xs = args[-1]
                # phase 1: halo on the wire (no dependence on compute)
                left, right = halo_parts(xs)
                # phase 2: interior tiles read only the local x slice
                y_int = launch(
                    a["i_vals"][0], a["i_lcol"][0], a["i_lrow"][0],
                    a["i_win"][0], a["i_cblk"][0], paste(xs, 0, Lp),
                    a["i_scale"][0] if has_scale else None,
                )
                # phase 3: boundary tiles consume the received halo window
                xw = paste(jnp.concatenate([left, xs, right]), H, Lp)
                y_bnd = launch(
                    a["b_vals"][0], a["b_lcol"][0], a["b_lrow"][0],
                    a["b_win"][0], a["b_cblk"][0], xw,
                    a["b_scale"][0] if has_scale else None,
                )
                return combine_tile_rows(
                    [y_int, y_bnd], [a["i_ids"][0], a["b_ids"][0]],
                    Tp, R, dtype=y_int.dtype,
                )

        else:
            names = ["vals", "lcol", "lrow", "win", "cblk"]
            if has_scale:
                names += ["scale"]

            def body(*args):
                a = dict(zip(names, args[:-1]))
                xp = distribute_x(args[-1], Lp)
                return launch(
                    a["vals"][0], a["lcol"][0], a["lrow"][0], a["win"][0],
                    a["cblk"][0], xp, a["scale"][0] if has_scale else None,
                )

        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis),) * len(names) + (x_spec,),
            out_specs=P(axis), check_vma=False,
        )
        arg_arrays = tuple(arrs[k] for k in names)
        rem = tiles.remainder_nnz
        rem_row, rem_col, rem_val = tiles.rem_row, tiles.rem_col, tiles.rem_val

        def call(*args):
            x = args[-1]
            xin = _pad_rows(x, Lp if strategy == "replicated" else D * Rs)
            y = f(*args[:-1], xin)[:m]
            if rem:
                rv = rem_val.astype(y.dtype)
                if x.ndim == 2:
                    rv = rv[:, None]
                y = y.at[rem_row].add(rv * x[rem_col].astype(y.dtype))
            return y

        return functools.partial(jax.jit(call), *arg_arrays)

    if base.backend == "sellcs":
        from repro.kernels.spmv_sellcs import spmv_sellcs_pallas

        st = base.sell_tiles
        n_pad = _round_up(max(st.shape[1], 1), _LANE)
        m_pad = int(st.row_perm.shape[0])
        row_perm = st.row_perm
        gather_mode, interpret = base.gather_mode, base.interpret
        chunk = base.params.gather_chunk
        has_scale = "scale" in arrs or "i_scale" in arrs

        def launch(v, c, xp, sc):
            return spmv_sellcs_pallas(
                v, c, xp, sc, gather_chunk=chunk,
                gather_mode=gather_mode, interpret=interpret,
            )

        if plan.overlap:
            Tp, C = plan.tiles_per_shard, plan.rows_per_tile
            names = ["i_vals", "i_cols", "i_ids", "b_vals", "b_cols", "b_ids"]
            if has_scale:
                names += ["i_scale", "b_scale"]

            def body(*args):
                a = dict(zip(names, args[:-1]))
                xs = args[-1]
                left, right = halo_parts(xs)
                y_int = launch(
                    a["i_vals"][0], a["i_cols"][0], paste(xs, 0, n_pad),
                    a["i_scale"][0] if has_scale else None,
                )
                xw = paste(jnp.concatenate([left, xs, right]), H, n_pad)
                y_bnd = launch(
                    a["b_vals"][0], a["b_cols"][0], xw,
                    a["b_scale"][0] if has_scale else None,
                )
                return combine_tile_rows(
                    [y_int, y_bnd], [a["i_ids"][0], a["b_ids"][0]],
                    Tp, C, dtype=y_int.dtype,
                )

        else:
            names = ["vals", "cols"]
            if has_scale:
                names += ["scale"]

            def body(*args):
                a = dict(zip(names, args[:-1]))
                xp = distribute_x(args[-1], n_pad)
                return launch(
                    a["vals"][0], a["cols"][0], xp,
                    a["scale"][0] if has_scale else None,
                )

        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis),) * len(names) + (x_spec,),
            out_specs=P(axis), check_vma=False,
        )
        arg_arrays = tuple(arrs[k] for k in names)

        def call(*args):
            x = args[-1]
            xin = _pad_rows(x, n_pad if strategy == "replicated" else D * Rs)
            y_sorted = f(*args[:-1], xin)[:m_pad]     # σ-sorted row order
            out = jnp.zeros((m + 1,) + y_sorted.shape[1:], y_sorted.dtype)
            return out.at[row_perm].set(y_sorted)[:m]

        return functools.partial(jax.jit(call), *arg_arrays)

    # CSR-2 / CPU fallback: pure-jnp oracle inside shard_map (no tile view) —
    # the same plan executor the legacy dist_spmv_* shims use.
    S = op.c_csr
    f = _csr_plan_shard_map(plan, mesh, axis)

    def call(rp, ci, vl, x):
        xin = x if strategy == "replicated" else _pad_rows(x, D * Rs)
        return f(rp, ci, vl, xin)[:m]

    return functools.partial(jax.jit(call), S.row_ptr, S.col_idx, S.vals)


def shard_prepared(
    base,
    mesh: Mesh,
    *,
    axis: str = "data",
    x_strategy: str = "auto",
    A: CSRMatrix | None = None,
    halo_overlap: bool | None = None,
) -> ShardedPreparedSpMV:
    """Partition a single-device :class:`PreparedSpMV` across ``mesh``.

    This is the setup half of the distributed layer (``prepare(A, mesh=...)``
    calls it).  The base operator's kernel tile view is split into contiguous
    per-shard stacks — CSR-k: whole SSR tiles; SELL-C-σ: whole C-row chunks;
    CSR-2 (CPU): raw row blocks — so every shard runs the *same* kernel with
    the same static shapes as the global launch (the bit-for-bit property).

    Backends without a shardable tile view (``segsum``, ``diahybrid``, and
    CSR-k prepared without tiles) *decline* tile partitioning: rows fall to
    the CSR-2 raw-row fallback and execute per-shard through the segment-sum
    oracle inside ``shard_map``.  The decline is observable — a
    ``distributed/tile_decline.<backend>`` counter fires and the per-shard
    registry decisions are still recorded in ``shard_backends``.  A
    compiled (TPU) segsum or diahybrid operator refuses instead: there the
    decline would swap its kernel for the oracle.

    On top of the partition, a :class:`ShardPlan` is built: per-tile column
    reach classifies each shard's tiles as interior or boundary, the halo
    edge schedule keeps only the sides boundary tiles actually read, and —
    when the halo strategy is active on a tile backend and enough tiles are
    interior — execution is staged so the interior launch overlaps the
    exchange.

    Args:
      base: the prepared single-device operator (any backend).
      mesh: the device mesh; rows are partitioned over ``axis``.
      axis: mesh axis name (default ``"data"``).
      x_strategy: ``"auto"`` (O(1) :func:`select_x_strategy` from the base
        stats), or one of ``"replicated" | "allgather" | "halo"``.  A halo
        request is demoted to allgather when a shard's real column reach
        exceeds one neighbour's rows (recorded in ``x_strategy_requested``).
      A: the source matrix in the *base operator's* index space (reordered
        for CSR-k, original for SELL-C-σ); used only to compute per-shard
        statistics for the registry's per-shard format decisions.  Falls back
        to the operator's own CSR view when available.
      halo_overlap: None (default) lets the plan decide — overlap when the
        halo strategy is active, the backend has a tile view, and at least
        ``OVERLAP_MIN_INTERIOR`` of the non-empty tiles are interior.  True
        forces overlap whenever it is structurally possible; False forces
        the blocking schedule (useful for A/B benchmarking — results are
        bit-for-bit identical either way).

    Returns:
      A :class:`ShardedPreparedSpMV`; call it like the base operator.
    """
    if x_strategy not in ("auto",) + X_STRATEGIES:
        raise ValueError(
            f"unknown x_strategy {x_strategy!r} (expected auto|" +
            "|".join(X_STRATEGIES) + ")"
        )
    D = int(mesh.shape[axis])

    # -- partition geometry + per-tile column reach -------------------------
    tile_backend = False
    sh = None
    if base.backend == "csrk" and base.tiles is not None:
        tiles = base.tiles
        T, R, W = tiles.num_tiles, tiles.rows_per_tile, tiles.window
        Tp = -(-T // D)
        Rs = Tp * R
        lo, hi = tiles.col_reach()
        tile_backend = True
        src = A if A is not None else base.csrk.csr
    elif base.backend == "sellcs":
        st = base.sell_tiles
        T, R = int(st.vals.shape[0]), int(st.vals.shape[1])   # R = chunk C
        Tp = -(-T // D)
        Rs = Tp * R
        lo, hi = st.col_reach()
        tile_backend = True
        src = A
    else:
        # CSR-2 fallback: no tile view — raw row partitioning + oracle.
        # segsum/diahybrid land here (their containers are not row-block
        # shardable), as does CSR-k prepared without tiles (cpu devices).
        # On a TPU that would replace their kernels by the oracle: refuse.
        if base.backend in ("segsum", "diahybrid") and not base.interpret:
            raise ValueError(
                f"the {base.backend} backend has no sharded kernel path; "
                "prepare it without mesh= on a TPU"
            )
        if A is not None:
            src = A
        elif base.csrk is not None:
            src = base.csrk.csr
        else:
            raise ValueError(
                f"backend {base.backend!r} has no shardable tile view and "
                "no CSR source; pass A= (prepare(A, mesh=...) does this)"
            )
        sh = shard_csr(src, D)
        Tp = R = 0
        Rs = sh.rows_per_shard

    # per-shard real-column extents (the only inputs the halo math needs)
    if tile_backend:
        reach = _shard_reach(lo, hi, Tp, D)
    else:
        rp = np.asarray(sh.row_ptr)
        ci = np.asarray(sh.col_idx)
        vl = np.asarray(sh.vals)
        reach = []
        for d in range(D):
            k = int(rp[d, -1])
            cols = ci[d, :k][vl[d, :k] != 0] if k else np.empty(0, np.int64)
            reach.append(
                (int(cols.min()), int(cols.max())) if len(cols) else None
            )

    # -- per-shard statistics + registry decisions (introspection) ----------
    # Uses the operator's actual (tile-granular) row partition, so the
    # recorded decisions describe the rows each shard really executes.
    # (SELL-C-σ shards own *σ-sorted* row blocks; the σ-window sort moves
    # rows at most σ positions, so the original-order block is the honest
    # host-side approximation.)
    if src is not None:
        from repro.sparse.registry import select_format

        shard_stats = compute_shard_stats(src, D, rows_per_shard=Rs)
        shard_backends = tuple(
            select_format(s, base.device) for s in shard_stats
        )
    else:
        shard_stats = (None,) * D
        shard_backends = (base.backend,) * D

    # -- x strategy resolution ----------------------------------------------
    stats = base.stats
    if stats is None and src is not None:
        from repro.sparse.stats import compute_stats

        stats = compute_stats(src)
    requested = x_strategy
    if x_strategy == "auto":
        if stats is not None:
            x_strategy = select_x_strategy(stats, D, Rs)
        else:
            x_strategy = "allgather"
    halo = 0
    demoted = False
    if x_strategy == "halo":
        H_req = _required_halo(reach, Rs, D)
        halo = max(_round_up(max(H_req, 1), _LANE), _LANE)
        if halo > Rs:
            # a shard reaches beyond its neighbours — halo cannot be exchanged
            # with a single ppermute pair; fall back to the O(n) gather.
            x_strategy, halo = "allgather", 0
            demoted = True

    # -- interior/boundary classification + overlap decision ----------------
    interior_ids: Tuple = ()
    boundary_ids: Tuple = ()
    interior_frac = 1.0
    left_edges: Tuple = ()
    right_edges: Tuple = ()
    overlap = False
    if tile_backend:
        interior_ids, boundary_ids, interior_frac = classify_tile_reach(
            lo, hi, tiles_per_shard=Tp, rows_per_shard=Rs, num_shards=D
        )
    if x_strategy == "halo":
        if tile_backend:
            left_edges, right_edges = _halo_edges(reach, Rs, D)
            # overlap needs at least one real interior tile (something to hide
            # the exchange behind) and one boundary tile (something to wait).
            can_overlap = 0.0 < interior_frac < 1.0
            if halo_overlap is None:
                overlap = can_overlap and interior_frac >= OVERLAP_MIN_INTERIOR
            else:
                overlap = bool(halo_overlap) and can_overlap
        else:
            # oracle fallback: single monolithic segment-sum — keep the
            # historical full-ring schedule (exact behaviour preservation).
            left_edges, right_edges = _ring_edges(D)

    plan = ShardPlan(
        strategy=x_strategy,
        num_shards=D,
        rows_per_shard=Rs,
        halo=halo,
        tiles_per_shard=Tp,
        rows_per_tile=R,
        overlap=overlap,
        interior_fraction=interior_frac,
        interior_ids=interior_ids,
        boundary_ids=boundary_ids,
        left_edges=left_edges,
        right_edges=right_edges,
    )

    # -- stack the kernel arrays in the layout the plan executes ------------
    arrs: dict = {}
    if base.backend == "csrk" and base.tiles is not None:
        v = np.asarray(tiles.vals)
        lc = np.asarray(tiles.local_col)
        lr = np.asarray(tiles.local_row)
        wb = np.asarray(tiles.win_block)
        scale = None if tiles.val_scale is None else np.asarray(tiles.val_scale)
        cblk = np.asarray(tiles.col_blocks)
        if overlap:
            Ti, Tb = plan.num_interior, plan.num_boundary
            for key, ids, T_sub in (("i", interior_ids, Ti),
                                    ("b", boundary_ids, Tb)):
                arrs[f"{key}_vals"] = _stack_tile_subset(v, ids, D, Tp, T_sub)
                arrs[f"{key}_lcol"] = _stack_tile_subset(lc, ids, D, Tp, T_sub)
                arrs[f"{key}_lrow"] = _stack_tile_subset(lr, ids, D, Tp, T_sub)
                arrs[f"{key}_win"] = _stack_tile_subset(wb, ids, D, Tp, T_sub)
                arrs[f"{key}_cblk"] = _stack_tile_subset(cblk, ids, D, Tp, T_sub)
                arrs[f"{key}_ids"] = _stack_subset_ids(ids, D, Tp, T_sub)
                if scale is not None:
                    arrs[f"{key}_scale"] = _stack_tile_subset(
                        scale, ids, D, Tp, T_sub
                    )
        else:
            arrs["vals"] = _stack_shards(v, D, Tp)
            arrs["lcol"] = _stack_shards(lc, D, Tp)
            arrs["lrow"] = _stack_shards(lr, D, Tp)
            arrs["win"] = _stack_shards(wb, D, Tp)
            arrs["cblk"] = _stack_shards(cblk, D, Tp)
            if scale is not None:
                arrs["scale"] = _stack_shards(scale, D, Tp)
    elif base.backend == "sellcs":
        v = np.asarray(st.vals)
        c = np.asarray(st.col_idx)
        scale = None if st.val_scale is None else np.asarray(st.val_scale)
        if overlap:
            Ti, Tb = plan.num_interior, plan.num_boundary
            for key, ids, T_sub in (("i", interior_ids, Ti),
                                    ("b", boundary_ids, Tb)):
                arrs[f"{key}_vals"] = _stack_tile_subset(v, ids, D, Tp, T_sub)
                arrs[f"{key}_cols"] = _stack_tile_subset(c, ids, D, Tp, T_sub)
                arrs[f"{key}_ids"] = _stack_subset_ids(ids, D, Tp, T_sub)
                if scale is not None:
                    arrs[f"{key}_scale"] = _stack_tile_subset(
                        scale, ids, D, Tp, T_sub
                    )
        else:
            arrs["vals"] = _stack_shards(v, D, Tp)
            arrs["cols"] = _stack_shards(c, D, Tp)
            if scale is not None:
                arrs["scale"] = _stack_shards(scale, D, Tp)

    # each stack [D, ...] lives one shard per device, so every shard's tiles
    # are resident where its kernel runs (no per-call reshard)
    placed = NamedSharding(mesh, P(axis))
    arrs = {k: jax.device_put(v, placed) for k, v in arrs.items()}

    # -- telemetry: the sharding decisions, as metrics rather than only as
    # operator attributes (docs/observability.md) ---------------------------
    reg = get_registry()
    if reg.enabled:
        reg.gauge("distributed", "num_shards", D, unit="count")
        reg.gauge("distributed", "rows_per_shard", Rs, unit="count")
        reg.gauge("distributed", "halo_rows", halo, unit="count")
        reg.gauge("distributed", "interior_fraction", interior_frac,
                  unit="fraction")
        reg.gauge("distributed", "collective_bytes",
                  plan.collective_bytes(), unit="bytes")
        reg.counter("distributed", f"x_strategy.{x_strategy}")
        if demoted:
            reg.counter("distributed", "halo_demoted_to_allgather")
        if x_strategy == "halo":
            reg.counter(
                "distributed",
                "halo_overlap.on" if overlap else "halo_overlap.off",
            )
        for b in shard_backends:
            reg.counter("distributed", f"shard_backend.{b}")
        if not tile_backend:
            reg.counter("distributed", f"tile_decline.{base.backend}")

    return ShardedPreparedSpMV(
        base=base,
        mesh=mesh,
        axis=axis,
        x_strategy_requested=requested,
        plan=plan,
        shard_stats=tuple(shard_stats),
        shard_backends=shard_backends,
        shard_arrays=arrs,
        c_csr=sh,
    )
