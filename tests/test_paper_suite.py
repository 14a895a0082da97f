"""Every matrix of the paper's Table 2, through ``prepare()``, against float64.

``configs.spmv_suite.SUITE`` holds the sixteen Table 2 entries as seeded
generators of the same family and row density.  At ``scale=4096`` each is
at most a few thousand rows, so the whole suite runs in the Pallas
interpreter.  Each case routes the matrix through ``prepare`` (the format
the registry picks, under both the default reorder and forced Band-k) and
checks ``apply_original`` row by row under the f32 bound of
``tests/_rowbound.py``.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from _rowbound import assert_rows_within_f32_bound
from repro.configs.spmv_suite import SUITE
from repro.core.spmv import prepare

SCALE = 4096


@pytest.mark.parametrize("reorder", ["auto", "bandk"])
@pytest.mark.parametrize("entry", SUITE, ids=lambda e: f"{e.id:02d}-{e.name}")
def test_suite_entry_matches_float64(entry, reorder):
    A = entry.build(SCALE)
    assert A.m <= 8192
    x = np.random.default_rng(entry.id).standard_normal(A.n).astype(np.float32)
    op = prepare(A, device="tpu_v5e", reorder=reorder)
    y = op.apply_original(jnp.asarray(x))
    assert y.shape == (A.m,)
    assert_rows_within_f32_bound(A, x, y)
