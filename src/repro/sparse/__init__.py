"""Sparse-format subsystem: containers, statistics and the format registry.

Grown out of the original ``repro.core.formats`` monolith (which re-exports
everything here for back-compat).  Layout:

* :mod:`repro.sparse.coo` / :mod:`repro.sparse.csr` — interchange formats
* :mod:`repro.sparse.csrk` — the paper's CSR-k + its TPU tile view
* :mod:`repro.sparse.quant` — grouped-scale int8 value streams
* :mod:`repro.sparse.sellcs` — SELL-C-σ for irregular matrices
* :mod:`repro.sparse.segsum` — speculative segmented-sum CSR (power-law path)
* :mod:`repro.sparse.diahybrid` — DIA + CSR remainder (stencil path)
* :mod:`repro.sparse.baselines` — ELL / BCSR / CSR5-like comparison formats
* :mod:`repro.sparse.stats` — one-pass matrix statistics
* :mod:`repro.sparse.registry` — O(1) ``select_format`` dispatch
"""
from repro.sparse.coo import COOMatrix  # noqa: F401
from repro.sparse.csr import CSRMatrix, csr_from_coo  # noqa: F401
from repro.sparse.csrk import (  # noqa: F401
    CSRkMatrix,
    CSRkTileBuckets,
    CSRkTiles,
    bucket_tiles,
    build_csrk,
    tiles_from_csrk,
)
from repro.sparse.baselines import (  # noqa: F401
    BCSRMatrix,
    CSR5LikeMatrix,
    ELLMatrix,
    bcsr_from_csr,
    csr5_from_csr,
    ell_from_csr,
)
from repro.sparse.sellcs import (  # noqa: F401
    SELLCSMatrix,
    SELLCSTiles,
    sellcs_from_csr,
    tiles_from_sellcs,
)
from repro.sparse.segsum import SegSumCSR, segsum_from_csr  # noqa: F401
from repro.sparse.diahybrid import (  # noqa: F401
    DIAHybridMatrix,
    dense_diagonals,
    diahybrid_from_csr,
)
from repro.sparse.stats import (  # noqa: F401
    DIA_FRACTION_MIN,
    DIAG_OCCUPANCY,
    REGULAR_ROW_VAR_MAX,
    SEGSUM_ROW_SKEW_MIN,
    MatrixStats,
    classify_tile_reach,
    compute_shard_stats,
    compute_stats,
)
from repro.sparse.registry import (  # noqa: F401
    FormatSpec,
    available_formats,
    get_format,
    register_format,
    select_format,
)
