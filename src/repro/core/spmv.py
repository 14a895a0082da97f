"""Format-dispatching SpMV public API — the paper's contribution as a module.

``prepare(A)`` runs the full setup pipeline and returns a
:class:`PreparedSpMV` whose ``__call__`` is a jit-compatible SpMV.

For the paper's CSR-k path (regular matrices):
  Band-k reorder (unless the input order already bounds the band) →
  constant-time tune (SSRS/SRS from rdensity) → CSR-k build → (TPU path)
  padded tile view.
The canonical CSR-k arrays stay CSR-compatible throughout (the heterogeneity
property); the device decides only the *interpretation*.

``format="auto"`` additionally runs the registry's O(1) selector
(:func:`repro.sparse.select_format`) over one-pass matrix statistics: regular
matrices (nnz/row variance ≤ 10, paper Sec. 6) keep the CSR-k path
bit-for-bit, irregular ones route to SELL-C-σ (Kreutzer et al.), power-law
irregular ones (row_skew ≥ 8) to the speculative segmented-sum CSR backend
(Liu & Vinter), and irregular-but-diagonal ones (diag_fraction ≥ 0.9) to the
DIA + CSR-remainder hybrid (Fukaya et al.).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.ordering as bandk_mod
import repro.core.tuner as tuner_mod
from repro.sparse import (
    DIAG_OCCUPANCY,
    CSRMatrix,
    CSRkMatrix,
    CSRkTileBuckets,
    CSRkTiles,
    DIAHybridMatrix,
    MatrixStats,
    SegSumCSR,
    SELLCSMatrix,
    SELLCSTiles,
    bucket_tiles,
    build_csrk,
    compute_stats,
    diahybrid_from_csr,
    segsum_from_csr,
    select_format,
    sellcs_from_csr,
    tiles_from_csrk,
    tiles_from_sellcs,
)
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.gather import WHOLE_X_MAX_COLS, pick_chunk, resolve_interpret
from repro.obs import annotate, get_registry


@dataclasses.dataclass(frozen=True)
class PreparedSpMV:
    """A tuned, reordered, device-ready SpMV operator y = A x.

    ``backend`` records which registered format won the dispatch ("csrk",
    "sellcs", "segsum" or "diahybrid"); ``stats`` holds the one-pass summary
    that drove the decision (None when the format was forced and stats were
    not needed).
    ``fingerprint`` is the content hash of the *source* matrix
    (:meth:`~repro.sparse.CSRMatrix.fingerprint`) stamped at ``prepare``
    time — the identity the serving layer's operator cache keys on.

    ``perm`` maps new index → old index (A was symmetrically permuted), so for
    callers living in the original index space:
        y_old[perm] == P A P^T (x_old[perm])  ⇒  use ``apply_original``.
    The SELL-C-σ, segsum and diahybrid paths never permute A (SELL's σ-sort
    is internal to its container; the other two consume CSR order directly),
    so there ``perm`` is the identity, as it is on the CSR-k path wherever
    ``reorder`` kept the input order.
    """

    csrk: Optional[CSRkMatrix]
    tiles: Optional[CSRkTiles]
    perm: np.ndarray
    params: tuner_mod.TuningParams
    device: str
    gather_mode: str = "onehot"
    interpret: Optional[bool] = None
    backend: str = "csrk"
    sell: Optional[SELLCSMatrix] = None
    sell_tiles: Optional[SELLCSTiles] = None
    stats: Optional[MatrixStats] = None
    tile_buckets: Optional[CSRkTileBuckets] = None
    value_dtype: str = "f32"
    fingerprint: Optional[str] = None
    spmm_width: Optional[int] = None
    segsum: Optional[SegSumCSR] = None
    dia: Optional[DIAHybridMatrix] = None

    def __post_init__(self):
        # Device-resident permutation arrays, built once at prepare() time so
        # apply_original never re-uploads host numpy per call.  argsort gives
        # the inverse permutation (inv[perm[i]] == i), turning the output
        # scatter into a cheaper gather with bit-identical placement.  An
        # identity ``perm`` keeps none: apply_original is then the call itself.
        perm_host = np.asarray(self.perm)
        identity = bool(np.array_equal(perm_host, np.arange(perm_host.size)))
        object.__setattr__(self, "_identity_perm", identity)
        object.__setattr__(self, "_perm_arrays", () if identity else (
            jnp.asarray(perm_host), jnp.asarray(np.argsort(perm_host))))

    @property
    def csr(self) -> CSRMatrix:
        if self.csrk is None:
            raise AttributeError(
                f"no CSR view: this operator uses the {self.backend!r} backend"
            )
        return self.csrk.csr

    def __call__(self, x: jax.Array) -> jax.Array:
        """SpMV / SpMM in the *reordered* index space.

        Args:
          x: a single vector of shape [n] or a multi-vector block [n, B].

        Returns:
          y = A x of shape [m] (resp. [m, B]).  The batched form streams the
          matrix exactly once for all B columns (SpMV is bandwidth-bound, so
          the extra right-hand sides are nearly free — the SELL-C-σ/CG
          amortization argument).

        With ``spmm_width=W`` set, every kernel launch is padded to exactly
        W columns (inputs wider than W are split into W-column launches):
        the launch shape is then a constant of the operator, so each output
        column's bits depend only on its own input column — the invariant
        that lets the serving engine coalesce requests into shared batches
        without changing any result (XLA picks contraction schedules per
        *shape*, so un-padded calls with different B may legitimately differ
        in final-ulp bits).  Unset (the default), calls dispatch at their
        natural width: fastest, and bit-stable per width.
        """
        if self.spmm_width is not None:
            W = self.spmm_width
            if x.ndim == 1:
                xw = jnp.zeros((x.shape[0], W), x.dtype).at[:, 0].set(x)
                return self._dispatch(xw)[:, 0]
            B = x.shape[1]
            outs = []
            for off in range(0, B, W):
                blk = x[:, off:off + W]
                if blk.shape[1] < W:
                    blk = jnp.pad(blk, ((0, 0), (0, W - blk.shape[1])))
                outs.append(self._dispatch(blk))
            Y = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
            return Y[:, :B]
        return self._dispatch(x)

    def launch(self):
        """The backend's kernel launch as ``(fn, operands)``.

        ``fn(operands, x)`` is a natural-width call (no ``spmm_width`` pad).
        ``operands`` is a pytree of the operator's device arrays, so
        ``jax.jit(fn).lower(operands, x)`` lowers the launch with those
        arrays as arguments rather than as baked-in constants.
        """
        chunk = self.params.gather_chunk
        tiled = dict(gather_mode=self.gather_mode, gather_chunk=chunk,
                     interpret=self.interpret)
        if self.backend == "sellcs":
            return functools.partial(kops.spmv_sellcs, **tiled), self.sell_tiles
        if self.backend == "segsum":
            return functools.partial(kops.spmv_segsum, **tiled), self.segsum
        if self.backend == "diahybrid":
            return (functools.partial(kops.spmv_diahybrid, interpret=self.interpret),
                    self.dia)
        if self.tile_buckets is not None:
            return (functools.partial(kops.spmv_csrk_bucketed, **tiled),
                    self.tile_buckets)
        if self.tiles is not None:
            return functools.partial(kops.spmv_csrk, **tiled), self.tiles
        # CPU path (CSR-2): hierarchy collapses to the segmented CSR kernel;
        # super-rows drive the parallel partitioning, which XLA:CPU derives
        # from the segment structure.
        return _csr_product, self.csr

    def _dispatch(self, x: jax.Array) -> jax.Array:
        """Backend kernel launch at x's natural width (no fixed-width pad)."""
        fn, operands = self.launch()
        return fn(operands, x)

    def matmat(self, X: jax.Array) -> jax.Array:
        """Explicit multi-vector alias: Y = A X for X of shape [n, B]."""
        if X.ndim != 2:
            raise ValueError(f"matmat expects a [n, B] block, got shape {X.shape}")
        return self(X)

    def apply_original(self, x_old: jax.Array) -> jax.Array:
        """SpMV / SpMM for vectors indexed in the matrix's original ordering.

        Args:
          x_old: [n] or [n, B] in the *original* (pre-reordering) index space.

        Returns:
          y = A x in the original index space, [m] resp. [m, B] — the
          permutation is applied on the way in and inverted on the way out
          using device-resident index arrays cached at ``prepare`` time.
          Where ``perm`` is the identity this is ``self(x_old)``, with no
          gathers and no permute spans.
        """
        with annotate("repro.apply_original"):
            if self._identity_perm:
                return self(x_old)
            perm, inv_perm = self._perm_arrays
            with annotate("repro.permute_in"):
                x_new = x_old[perm]
            y_new = self(x_new)
            with annotate("repro.permute_out"):
                return y_new[inv_perm]

    # -- introspection used by benchmarks ------------------------------------
    def overhead_fraction(self) -> float:
        if self.backend == "sellcs":
            base = (2 * self.sell.nnz + self.sell.m + 1) * 4
            return self.sell.overhead_bytes() / base
        if self.backend == "segsum":
            base = (2 * self.segsum.nnz + self.segsum.m + 1) * 4
            return self.segsum.overhead_bytes() / base
        if self.backend == "diahybrid":
            base = (2 * self.dia.nnz + self.dia.m + 1) * 4
            return self.dia.overhead_bytes() / base
        return self.csrk.overhead_fraction()

    def padding_overhead(self) -> float:
        if self.backend == "sellcs":
            return self.sell.padding_overhead()
        if self.backend == "segsum":
            return self.segsum.padding_overhead()
        if self.backend == "diahybrid":
            return self.dia.padding_overhead()
        return self.tiles.padding_overhead() if self.tiles is not None else 0.0

    def modeled_bytes(self) -> int:
        """Modeled HBM bytes one SpMV moves (the roofline numerator).

        Uses the executed layout: bucketed CSR-k sums per-bucket launches,
        monolithic uses worst-tile padding, SELL-C-σ uses chunk widths; the
        CPU/CSR fallback counts the raw CSR streams.
        """
        if self.backend == "sellcs":
            return self.sell_tiles.modeled_bytes()
        if self.backend == "segsum":
            return self.segsum.modeled_bytes()
        if self.backend == "diahybrid":
            return self.dia.modeled_bytes()
        if self.tile_buckets is not None:
            return self.tile_buckets.modeled_bytes()
        if self.tiles is not None:
            return self.tiles.modeled_bytes()
        m, n = self.csrk.shape
        return self.csrk.nnz * 8 + (m + 1) * 4 + m * 4 + n * 4

    def resident_bytes(self) -> int:
        """Total bytes this operator keeps resident between calls.

        Sums the array leaves of every container the operator holds (canonical
        CSR-k/SELL arrays, the kernel tile views, the cached permutation
        arrays) — an upper bound on the footprint one cached operator costs,
        which is what the serving layer's byte-budget LRU
        (:class:`repro.serve.OperatorCache`) charges against.
        """
        leaves = jax.tree_util.tree_leaves((
            self.csrk, self.tiles, self.tile_buckets, self.sell,
            self.sell_tiles, self.segsum, self.dia, self._perm_arrays,
        ))
        return sum(int(leaf.nbytes) for leaf in leaves
                   if hasattr(leaf, "nbytes"))


def _csr_product(csr: CSRMatrix, x: jax.Array) -> jax.Array:
    return kref.spmm_csr(csr, x) if x.ndim == 2 else kref.spmv_csr(csr, x)


def _record_prepared(op: PreparedSpMV) -> PreparedSpMV:
    """Record setup telemetry for a freshly built operator (docs/observability.md).

    Emits the device-upload phase timing (blocking until the kernel-view
    arrays are resident — the cost callers actually pay before the first
    SpMV) plus structural gauges: padding overhead, pointer overhead, tile
    count and a per-backend counter.  Purely observational: the operator is
    returned unchanged, and nothing here runs when telemetry is disabled.
    """
    reg = get_registry()
    if not reg.enabled:
        return op
    with reg.timer("prepare", "phase.device_upload"):
        if op.backend == "sellcs":
            uploads = (op.sell_tiles.vals, op.sell_tiles.col_idx)
        elif op.backend == "segsum":
            uploads = (op.segsum.vals, op.segsum.col_idx,
                       op.segsum.local_seg, op.segsum.seg_row)
        elif op.backend == "diahybrid":
            uploads = (op.dia.diag_vals, op.dia.remainder.vals,
                       op.dia.remainder.col_idx)
        elif op.tiles is not None:
            uploads = (op.tiles.vals, op.tiles.local_col,
                       op.tiles.local_row, op.tiles.win_block,
                       op.tiles.col_blocks)
        else:
            uploads = (op.csrk.csr.vals, op.csrk.csr.col_idx)
        for arr in uploads + op._perm_arrays:
            jax.block_until_ready(arr)
    reg.counter("prepare", f"backend.{op.backend}")
    reg.gauge("prepare", "padding_overhead", op.padding_overhead(),
              unit="fraction")
    reg.gauge("prepare", "overhead_fraction", op.overhead_fraction(),
              unit="fraction")
    if op.backend == "sellcs":
        tile_count = int(op.sell_tiles.vals.shape[0])      # C-row chunks
    elif op.backend == "segsum":
        tile_count = op.segsum.num_chunks                  # nnz chunks
    elif op.backend == "diahybrid":
        tile_count = op.dia.n_diag                         # dense diagonals
    else:
        tile_count = op.tiles.num_tiles if op.tiles is not None else 0
    reg.gauge("prepare", "tile_count", tile_count, unit="count")
    if op.backend == "csrk" and op.tiles is not None:
        # one-hot chunks the CSR-k kernel visits, as a share of a full sweep
        # of every tile's 2·window (bucketing keeps each tile's table row)
        chunk = pick_chunk(op.tiles.window, op.params.gather_chunk)
        sweep = op.tiles.num_tiles * (2 * op.tiles.window // chunk)
        reg.gauge("prepare", "csrk.onehot_share",
                  100.0 * op.tiles.chunks_visited(chunk).sum() / max(sweep, 1),
                  unit="%")
    if op.stats is not None:
        reg.gauge("prepare", "stats.row_var", op.stats.row_var)
        reg.gauge("prepare", "stats.bandwidth", op.stats.bandwidth,
                  unit="count")
    return op


def _auto_value_dtype(
    A: CSRMatrix,
    stats: Optional[MatrixStats],
    candidates: tuple = ("int8", "bf16"),
) -> str:
    """Pick the cheapest value dtype whose SpMV error clears the bound.

    One host-side probe SpMV against a fixed random x per candidate — int8
    (grouped scales) is tried first, then bf16; the tolerance is half the
    acceptance bound (int8 ≤ 2.5e-2, bf16 ≤ 5e-3 relative) so suite noise
    cannot push an auto-routed matrix over the documented limit.  ``stats``
    (when the auto-format pass already computed them) short-circuits the
    probe for tiny matrices where compression cannot pay for its scales.
    ``candidates`` restricts the dtypes a backend supports (the diahybrid
    plane has no slot grouping for int8 scales, so it probes bf16 only).
    """
    from repro.optim.compress import (
        INT8_GROUP, dequantize_int8_grouped, quantize_int8_grouped,
    )

    nnz = A.nnz
    if nnz < 4 * INT8_GROUP or (stats is not None and stats.nnz < 4 * INT8_GROUP):
        return "f32"
    vl = np.asarray(A.vals, np.float32)
    ci = np.asarray(A.col_idx)
    rp = np.asarray(A.row_ptr)
    rows = np.repeat(np.arange(A.m), rp[1:] - rp[:-1])
    rng = np.random.default_rng(0)
    x = rng.standard_normal(A.shape[1]).astype(np.float32)
    y = np.zeros(A.m, np.float32)
    np.add.at(y, rows, vl * x[ci])
    scale = max(float(np.linalg.norm(y)), 1e-30)

    if "int8" in candidates:
        pad = (-nnz) % INT8_GROUP
        vpad = np.pad(vl, (0, pad))
        q, s = quantize_int8_grouped(vpad, group=INT8_GROUP)
        v8 = dequantize_int8_grouped(q, s, group=INT8_GROUP)[:nnz]
        y8 = np.zeros(A.m, np.float32)
        np.add.at(y8, rows, v8 * x[ci])
        if np.linalg.norm(y8 - y) / scale <= 2.5e-2:
            return "int8"
    if "bf16" in candidates:
        v16 = np.asarray(jnp.asarray(vl).astype(jnp.bfloat16).astype(jnp.float32))
        y16 = np.zeros(A.m, np.float32)
        np.add.at(y16, rows, v16 * x[ci])
        if np.linalg.norm(y16 - y) / scale <= 5e-3:
            return "bf16"
    return "f32"


def prepare(
    A: CSRMatrix,
    device: str = "tpu_v5e",
    *,
    format: str = "auto",             # "auto" | "csrk" | "sellcs" | "segsum" | "diahybrid"
    reorder: str = "auto",            # "auto" | "bandk" | "rcm" | "natural"
    params: tuner_mod.TuningParams | None = None,
    gather_mode: str = "onehot",
    gather_chunk: int | None = None,
    interpret: bool | None = None,
    adaptive: bool = False,
    sell_c: int = 8,
    sell_sigma: int | None = None,
    segsum_chunk: int = 512,
    diag_occupancy: float = DIAG_OCCUPANCY,
    value_dtype: str = "f32",         # "f32" | "bf16" | "int8" | "auto"
    tile_layout: str = "bucketed",    # "bucketed" | "monolithic"
    spmm_width: int | None = None,
    mesh=None,
    shard_axis: str = "data",
    x_strategy: str = "auto",
    halo_overlap: bool | None = None,
):
    """Full heterogeneous SpMV setup pipeline (paper Sec. 3–4 + registry).

    Args:
      A: the matrix, as a :class:`~repro.sparse.CSRMatrix` of shape [m, n].
      device: target device model ("tpu_v5e" | "volta" | "ampere" | "cpu" |
        "rome" | "icelake") — drives the constant-time tuner and the format
        selector.
      format: storage backend selection:

        * ``"auto"`` — compute one-pass :class:`~repro.sparse.MatrixStats`
          (nnz/row mean + variance, rdensity, diag_fraction, row_skew, the
          bandwidth in the order the operator uses) and dispatch via the
          registry's O(1) :func:`~repro.sparse.select_format`: matrices
          with nnz/row variance ≤ 10 (the paper's Sec. 6 regularity bound)
          take the CSR-k path, bit-for-bit identical to ``format="csrk"``;
          irregular matrices take SELL-C-σ, unless they are power-law
          skewed (row_skew ≥ 8 → ``segsum``) or near-fully diagonal
          (diag_fraction ≥ 0.9 → ``diahybrid``).
        * ``"csrk"`` — force the paper's path: reorder (``reorder``) →
          constant-time tune from rdensity → CSR-k build → padded tile view.
        * ``"sellcs"`` — force SELL-C-σ: σ-window sort → C-row chunks →
          per-chunk padded slices → uniform-width Pallas view.  No Band-k
          (the σ-sort is the reordering; ``perm`` stays identity).
        * ``"segsum"`` — force the speculative segmented-sum CSR backend
          (Liu & Vinter): equal-nnz chunks independent of row boundaries +
          a carry/patch scatter — O(nnz) regardless of row-length skew or
          empty rows.  ``perm`` stays identity.
        * ``"diahybrid"`` — force the partially-diagonal hybrid (Fukaya et
          al.): diagonals with occupancy ≥ ``diag_occupancy`` become a DIA
          plane (shifted dense contraction in Pallas), the rest rides the
          CSR oracle path.  ``perm`` stays identity.
      reorder: global reordering for the CSR-k path:

        * ``"auto"`` (default) — keep the input order where it already bounds
          the band: when the input's bandwidth is at most
          ``max_k(|L_k| + |L_{k+1}|) − 1`` over the breadth-first level sets
          from a pseudo-peripheral node (the bandwidth a Cuthill–McKee-family
          order can be expected to reach; the largest over the connected
          components), ``perm`` is the identity and ``apply_original``
          gathers nothing; otherwise Band-k, as ``"bandk"``.  The decision
          costs O(nnz) and sets the gauges ``prepare/reorder.kept_input_order``,
          ``reorder.band_input`` and ``reorder.band_levels``.
        * ``"bandk"`` — the paper's Band-k (k = 3), whatever the input order.
        * ``"rcm"`` — reverse Cuthill–McKee.
        * ``"natural"`` — the input order.
      params: explicit :class:`~repro.core.tuner.TuningParams`; None runs the
        constant-time tuner.
      gather_mode: in-kernel x-gather ("onehot" MXU matmuls | "take").
        "take" has no Mosaic lowering: it runs in interpret mode only, and
        a TPU prepare refuses it.
      gather_chunk: one-hot gather chunk width (a 128 multiple).  None defers
        to the tuner (``TuningParams.gather_chunk``, which the fitted device
        model can set); an explicit value overrides both.
      interpret: Pallas execution mode.  None (default) follows the
        platform: the CPU interprets the kernels, a TPU compiles them, any
        other platform raises ``ValueError``.
      adaptive: replace the paper's rdensity-only formula with the
        variance-aware bytes-model tuner (beyond-paper; CSR-k path only).
      sell_c / sell_sigma: SELL-C-σ chunk height and sorting window
        (defaults: C=8 sublanes, σ=16·C).
      segsum_chunk: segsum nnz slots per chunk (rounded up to a 128-lane
        multiple; segsum backend only).
      diag_occupancy: dense-diagonal extraction threshold for the diahybrid
        backend (defaults to the stats pass's
        :data:`~repro.sparse.DIAG_OCCUPANCY`, keeping the routing signal and
        the container in agreement).
      value_dtype: storage dtype of the kernel value stream — "f32" (exact),
        "bf16" (2 B/value), "int8" (1 B/value + one f32 scale per 128-slot
        group, the grouped-scale idiom from :mod:`repro.optim.compress`), or
        "auto" (probe SpMV picks the cheapest dtype within the documented
        error bounds: int8 ≤ 2.5e-2, bf16 ≤ 5e-3 relative).  Accumulation is
        always f32; indices and the COO remainder are unaffected.  The
        CPU/CSR-2 fallback path always computes in f32.
      tile_layout: CSR-k tile memory layout — "bucketed" (default: tiles
        grouped by rounded-up nnz, one Pallas launch per slot bucket;
        bit-for-bit identical to monolithic for f32, strictly fewer HBM
        bytes whenever tile nnz varies) or "monolithic" (single launch,
        every tile padded to the worst tile's slots).
      spmm_width: when set to W ≥ 1, pad every kernel launch to exactly W
        columns (and split wider inputs into W-column launches).  Fixes the
        launch shape so each output column is bit-independent of its batch
        neighbours — required by the serving engine's coalescing contract
        (``repro.serve``); costs one W-wide launch even for single vectors.
        None (default) dispatches at natural width.  Single-device operators
        only (the ``mesh=`` path ignores it).
      mesh: optional :class:`jax.sharding.Mesh`.  When given, the prepared
        operator is partitioned over ``shard_axis`` and returned as a
        :class:`~repro.core.distributed.ShardedPreparedSpMV` — same call
        surface, bit-for-bit identical results, Pallas kernels running
        inside ``shard_map``.
      shard_axis: mesh axis name rows are partitioned over (default "data").
      x_strategy: x distribution for the sharded operator: "auto" (O(1)
        selection from the matrix stats), "replicated", "allgather" or
        "halo".  Ignored when ``mesh`` is None.
      halo_overlap: staged halo execution for the sharded operator: None
        (default) lets the :class:`~repro.core.distributed.ShardPlan` decide
        from the interior tile fraction, True forces overlap when possible,
        False forces the blocking schedule.  Ignored when ``mesh`` is None.

    Returns:
      A :class:`PreparedSpMV` (or :class:`ShardedPreparedSpMV` when ``mesh``
      is given) whose ``__call__`` maps x of shape [n] or [n, B] to y of
      shape [m] resp. [m, B] in the reordered index space;
      ``apply_original`` works in the matrix's original index space.

    Raises:
      ValueError: for an unknown option, ``gather_mode="take"`` on a TPU,
        or a SELL-C-σ / segsum / diahybrid matrix wider than its kernel's
        whole-x limit (:data:`repro.kernels.gather.WHOLE_X_MAX_COLS`).
    """
    interpret = resolve_interpret(interpret)
    if gather_mode not in ("onehot", "take"):
        raise ValueError(f"unknown gather_mode {gather_mode!r} (expected onehot|take)")
    if gather_mode == "take" and not interpret:
        raise ValueError(
            'gather_mode="take" has no Mosaic lowering; on a TPU use "onehot"'
        )
    if mesh is not None:
        # The sharded operator partitions the *monolithic* tile view (whole
        # tiles per shard), so the bucketed layout is not built here.
        base = prepare(
            A, device, format=format, reorder=reorder, params=params,
            gather_mode=gather_mode, gather_chunk=gather_chunk,
            interpret=interpret, adaptive=adaptive,
            sell_c=sell_c, sell_sigma=sell_sigma,
            segsum_chunk=segsum_chunk, diag_occupancy=diag_occupancy,
            value_dtype=value_dtype, tile_layout="monolithic",
        )
        from repro.core.distributed import shard_prepared

        src = base.csrk.csr if base.backend == "csrk" else A
        return shard_prepared(
            base, mesh, axis=shard_axis, x_strategy=x_strategy, A=src,
            halo_overlap=halo_overlap,
        )
    if tile_layout not in ("bucketed", "monolithic"):
        raise ValueError(
            f"unknown tile_layout {tile_layout!r} (expected bucketed|monolithic)"
        )
    if spmm_width is not None and spmm_width < 1:
        raise ValueError(f"spmm_width must be >= 1, got {spmm_width}")
    reg = get_registry()
    # Content hash of the *input* matrix (pre-reordering): the identity the
    # serving layer's operator cache keys on.  O(nnz) host-side, setup only.
    fingerprint = A.fingerprint()
    stats = None
    if format == "auto":
        with reg.timer("prepare", "phase.stats"):
            stats = compute_stats(A)
            format = select_format(stats, device)
    if format in WHOLE_X_MAX_COLS:
        cols = max(A.shape) if format == "diahybrid" else A.shape[1]
        if cols > WHOLE_X_MAX_COLS[format]:
            raise ValueError(
                f"the {format} kernel holds x whole in VMEM and addresses at "
                f"most {WHOLE_X_MAX_COLS[format]} columns; this matrix needs "
                f"{cols} (shape {A.shape})"
            )
    if value_dtype == "auto":
        with reg.timer("prepare", "phase.value_dtype"):
            # the diahybrid plane has no slot grouping → no int8 scales
            cands = ("bf16",) if format == "diahybrid" else ("int8", "bf16")
            value_dtype = _auto_value_dtype(A, stats, candidates=cands)
        reg.counter("prepare", f"value_dtype.{value_dtype}")
    if format == "sellcs":
        with reg.timer("prepare", "phase.tile_build"):
            sell = sellcs_from_csr(A, C=sell_c, sigma=sell_sigma)
            sell_tiles = tiles_from_sellcs(sell, value_dtype=value_dtype)
        sell_params = tuner_mod.TuningParams(
            ssrs=1, srs=sell_c, k=1, use_inner_parallel=True
        )
        if gather_chunk is not None:
            sell_params = dataclasses.replace(sell_params, gather_chunk=gather_chunk)
        return _record_prepared(PreparedSpMV(
            csrk=None,
            tiles=None,
            perm=np.arange(A.m),
            params=sell_params,
            device=device,
            gather_mode=gather_mode,
            interpret=interpret,
            backend="sellcs",
            sell=sell,
            sell_tiles=sell_tiles,
            stats=stats,
            value_dtype=value_dtype,
            fingerprint=fingerprint,
            spmm_width=spmm_width,
        ))
    if format in ("segsum", "diahybrid"):
        ident_params = tuner_mod.TuningParams(
            ssrs=1, srs=1, k=1, use_inner_parallel=True
        )
        if gather_chunk is not None:
            ident_params = dataclasses.replace(
                ident_params, gather_chunk=gather_chunk
            )
        with reg.timer("prepare", "phase.tile_build"):
            if format == "segsum":
                seg = segsum_from_csr(
                    A, chunk_slots=segsum_chunk, value_dtype=value_dtype
                )
                dia = None
            else:
                seg = None
                dia = diahybrid_from_csr(
                    A, occupancy=diag_occupancy, value_dtype=value_dtype
                )
        return _record_prepared(PreparedSpMV(
            csrk=None,
            tiles=None,
            perm=np.arange(A.m),
            params=ident_params,
            device=device,
            gather_mode=gather_mode,
            interpret=interpret,
            backend=format,
            segsum=seg,
            dia=dia,
            stats=stats,
            value_dtype=value_dtype,
            fingerprint=fingerprint,
            spmm_width=spmm_width,
        ))
    if format != "csrk":
        raise ValueError(
            f"unknown format {format!r} "
            "(expected auto|csrk|sellcs|segsum|diahybrid)"
        )

    with reg.timer("prepare", "phase.reorder"):
        if reorder == "auto":
            keep, band_input, band_levels = bandk_mod.keeps_input_order(A)
            reg.gauge("prepare", "reorder.kept_input_order", float(keep), unit="flag")
            reg.gauge("prepare", "reorder.band_input", band_input, unit="count")
            reg.gauge("prepare", "reorder.band_levels", band_levels, unit="count")
            reorder = "natural" if keep else "bandk"
        if reorder == "bandk":
            perm = bandk_mod.bandk(A, k=3)
        elif reorder == "rcm":
            perm = bandk_mod.rcm(A)
        elif reorder == "natural":
            perm = np.arange(A.m)
        else:
            raise ValueError(f"unknown reorder {reorder!r}")
        Ar = A
        if reorder != "natural":
            with reg.timer("prepare", "phase.reorder.symperm"):
                Ar = A.symmetric_permute(perm)
                if stats is not None:
                    # report the post-reordering bandwidth (row-length stats
                    # are permutation-invariant, so routing is unaffected)
                    stats = compute_stats(Ar)

    with reg.timer("prepare", "phase.tune"):
        if params is None:
            if adaptive and device == "tpu_v5e":
                params = tuner_mod.tune_tpu_adaptive(
                    np.asarray(Ar.row_ptr), np.asarray(Ar.col_idx), Ar.rdensity, Ar.m
                )
            else:
                params = tuner_mod.tune(Ar.rdensity, device=device, m=Ar.m)
        if gather_chunk is not None:
            params = dataclasses.replace(params, gather_chunk=gather_chunk)

    with reg.timer("prepare", "phase.tile_build"):
        if params.k >= 3 and device not in ("cpu", "rome", "icelake"):
            csrk = build_csrk(Ar, srs=params.srs, ssrs=params.ssrs, k=3)
            tiles = tiles_from_csrk(csrk, value_dtype=value_dtype)
            buckets = bucket_tiles(tiles) if tile_layout == "bucketed" else None
        else:
            csrk = build_csrk(Ar, srs=params.srs, k=2)
            tiles = None
            buckets = None
            value_dtype = "f32"   # CSR-2/CPU fallback computes on raw CSR
    return _record_prepared(PreparedSpMV(
        csrk=csrk,
        tiles=tiles,
        perm=perm,
        params=params,
        device=device,
        gather_mode=gather_mode,
        interpret=interpret,
        backend="csrk",
        stats=stats,
        tile_buckets=buckets,
        value_dtype=value_dtype,
        fingerprint=fingerprint,
        spmm_width=spmm_width,
    ))


def spmv(A: CSRMatrix, x: jax.Array) -> jax.Array:
    """One-shot CSR SpMV (no setup) — the plain-CSR baseline.

    Args:
      A: CSR matrix of shape [m, n].
      x: vector of shape [n].

    Returns:
      y = A x of shape [m], computed with the pure-jnp segmented oracle.
    """
    return kref.spmv_csr(A, x)


def spmm(A: CSRMatrix, X: jax.Array) -> jax.Array:
    """One-shot CSR SpMM (no setup): Y = A X.

    Args:
      A: CSR matrix of shape [m, n].
      X: multi-vector block of shape [n, B] (raises otherwise).

    Returns:
      Y of shape [m, B]; the matrix nnz stream is read once for all B
      right-hand sides.
    """
    if X.ndim != 2:
        raise ValueError(f"spmm expects X of shape [n, B], got {X.shape}")
    return kref.spmm_csr(A, X)
