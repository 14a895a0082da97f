"""The adversarial families under every format ``prepare()`` accepts for them.

``powerlaw_zipf`` (hub rows and empty rows), ``pareto_rows`` (capped heavy
tail) and ``stencil_fringe`` (dense diagonals plus long-range fringe) each
sit at one of the format selector's boundaries.  Whatever backend is forced,
``apply_original`` must still be the product: each case checks it row by
row against float64 under the f32 bound of ``tests/_rowbound.py``.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from _rowbound import assert_rows_within_f32_bound
from repro.configs.spmv_suite import ADVERSARIAL, pareto_rows
from repro.core.spmv import prepare

SCALE = 4096
FAMILIES = {
    "powerlaw_zipf": lambda: ADVERSARIAL["powerlaw_zipf"](SCALE),
    "pareto_rows": lambda: pareto_rows(2048),
    "stencil_fringe": lambda: ADVERSARIAL["stencil_fringe"](SCALE),
}


@pytest.mark.parametrize("fmt", ["csrk", "sellcs", "segsum", "diahybrid"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_forced_format_matches_float64(family, fmt):
    A = FAMILIES[family]()
    x = np.random.default_rng(0).standard_normal(A.n).astype(np.float32)
    op = prepare(A, device="tpu_v5e", format=fmt)
    assert op.backend == fmt
    assert_rows_within_f32_bound(A, x, op.apply_original(jnp.asarray(x)))
