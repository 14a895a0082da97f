"""Mixed-precision value streams (f32 / bf16 / int8-grouped-scale).

Acceptance bounds from the issue: relative L2 error ≤ 1e-2 for bf16 and
≤ 5e-2 for int8, for BOTH kernel paths (CSR-k tiles and SELL-C-σ), exercised
through ``prepare(..., value_dtype=...)``.  Cross-format comparisons go
through ``apply_original`` — the CSR-k operator computes in the reordered
index space, SELL-C-σ in the original one.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.formats import (CSRMatrix, build_csrk, sellcs_from_csr,
                                tiles_from_csrk, tiles_from_sellcs)
from repro.core.spmv import prepare
from repro.kernels import ops, ref
from repro.optim.compress import (INT8_GROUP, dequantize_int8_grouped,
                                  quantize_int8_grouped)

from _close import assert_reordered_sum_close

BOUNDS = {"f32": 1e-5, "bf16": 1e-2, "int8": 5e-2}


def _case(rng, m=96, n=96, density=0.08):
    dense = ((rng.random((m, n)) < density) * rng.standard_normal((m, n)))
    dense = dense.astype(np.float32)
    A = CSRMatrix.fromdense(dense)
    x = rng.standard_normal(n).astype(np.float32)
    return A, dense, x


def _rel_err(y, y_ref):
    y = np.asarray(y, np.float64)
    y_ref = np.asarray(y_ref, np.float64)
    return float(np.linalg.norm(y - y_ref) / max(np.linalg.norm(y_ref), 1e-30))


@pytest.mark.parametrize("fmt", ["csrk", "sellcs"])
@pytest.mark.parametrize("vd", ["f32", "bf16", "int8"])
def test_prepare_value_dtype_error_bounds(rng, fmt, vd):
    A, dense, x = _case(rng)
    op = prepare(A, device="tpu_v5e", reorder="bandk", format=fmt,
                 value_dtype=vd)
    assert op.value_dtype == vd
    y = op.apply_original(jnp.asarray(x))
    assert _rel_err(y, dense @ x) <= BOUNDS[vd], (fmt, vd)


@pytest.mark.parametrize("vd", ["bf16", "int8"])
def test_csrk_kernel_matches_dtype_aware_oracle_exactly(rng, vd):
    """The oracle mirrors the in-kernel dequantization — the same products,
    summed in another order (see tests/_close.py)."""
    A, _, x = _case(rng)
    tiles = tiles_from_csrk(build_csrk(A, srs=4, ssrs=2, k=3), value_dtype=vd)
    assert (tiles.val_scale is not None) == (vd == "int8")
    y_k = ops.spmv_csrk(tiles, jnp.asarray(x), interpret=True)
    y_o = ref.spmv_csrk_tiles(tiles, jnp.asarray(x))
    assert_reordered_sum_close(y_k, y_o)


@pytest.mark.parametrize("vd", ["bf16", "int8"])
def test_sellcs_kernel_matches_dtype_aware_oracle_exactly(rng, vd):
    A, _, x = _case(rng, density=0.05)
    st = tiles_from_sellcs(sellcs_from_csr(A), value_dtype=vd)
    y_k = ops.spmv_sellcs(st, jnp.asarray(x), interpret=True)
    y_o = ref.spmv_sellcs_tiles(st, jnp.asarray(x))
    assert_reordered_sum_close(y_k, y_o)


def test_int8_grouped_quantization_roundtrip(rng):
    """Per-group error bound: |dq − v| ≤ group amax / 127 elementwise."""
    v = rng.standard_normal((4, 4 * INT8_GROUP)).astype(np.float32)
    v[0, :INT8_GROUP] = 0.0                      # all-zero group → scale 1.0
    q, scales = quantize_int8_grouped(v, group=INT8_GROUP)
    assert q.dtype == np.int8 and scales.shape == (4, 4)
    dq = dequantize_int8_grouped(q, scales, group=INT8_GROUP)
    amax = np.abs(v).reshape(4, 4, INT8_GROUP).max(axis=-1)
    bound = np.repeat(amax / 127.0, INT8_GROUP, axis=-1).reshape(v.shape)
    assert np.all(np.abs(dq - v) <= bound + 1e-7)
    np.testing.assert_array_equal(dq[0, :INT8_GROUP], 0.0)


def test_modeled_bytes_shrink_with_narrower_dtypes(rng):
    A, _, _ = _case(rng)
    sizes = {}
    for vd in ("f32", "bf16", "int8"):
        op = prepare(A, device="tpu_v5e", reorder="bandk", format="csrk",
                     value_dtype=vd)
        sizes[vd] = op.modeled_bytes()
    assert sizes["int8"] < sizes["bf16"] < sizes["f32"], sizes
    # same ordering on the SELL-C-σ view
    sell_sizes = {
        vd: tiles_from_sellcs(sellcs_from_csr(A), value_dtype=vd).modeled_bytes()
        for vd in ("f32", "bf16", "int8")
    }
    assert sell_sizes["int8"] < sell_sizes["bf16"] < sell_sizes["f32"]


def test_auto_value_dtype_respects_bound(rng):
    A, dense, x = _case(rng, m=128, n=128, density=0.1)
    op = prepare(A, device="tpu_v5e", reorder="bandk", format="csrk",
                 value_dtype="auto")
    assert op.value_dtype in ("f32", "bf16", "int8")
    y = op.apply_original(jnp.asarray(x))
    assert _rel_err(y, dense @ x) <= BOUNDS[op.value_dtype]


def test_auto_keeps_tiny_matrices_f32():
    """Below 4 scale groups of nnz the scales don't pay for themselves."""
    dense = np.eye(16, dtype=np.float32)
    op = prepare(CSRMatrix.fromdense(dense), device="tpu_v5e",
                 format="csrk", value_dtype="auto")
    assert op.value_dtype == "f32"


def test_unknown_value_dtype_raises(rng):
    A, _, _ = _case(rng, m=32, n=32)
    with pytest.raises(ValueError, match="value_dtype"):
        prepare(A, device="tpu_v5e", format="csrk", value_dtype="fp8")


def test_int8_batched_paths_consistent(rng):
    """[n, B] batched SpMM under int8 equals B single-vector applies."""
    A, _, _ = _case(rng)
    op = prepare(A, device="tpu_v5e", reorder="bandk", format="csrk",
                 value_dtype="int8")
    X = jnp.asarray(rng.standard_normal((A.n, 3)).astype(np.float32))
    Y = op.apply_original(X)
    for j in range(3):
        yj = op.apply_original(X[:, j])
        np.testing.assert_allclose(np.asarray(Y[:, j]), np.asarray(yj),
                                   rtol=1e-5, atol=1e-6)


def test_bucketed_int8_matches_monolithic_bitwise(rng):
    """Mixed precision composes with slot bucketing: still bit-identical."""
    from repro.core.formats import bucket_tiles

    A, _, x = _case(rng)
    tiles = tiles_from_csrk(build_csrk(A, srs=4, ssrs=2, k=3),
                            value_dtype="int8")
    buckets = bucket_tiles(tiles)
    y_m = ops.spmv_csrk(tiles, jnp.asarray(x), interpret=True)
    y_b = ops.spmv_csrk_bucketed(buckets, jnp.asarray(x), interpret=True)
    np.testing.assert_array_equal(
        np.asarray(y_m).view(np.int32), np.asarray(y_b).view(np.int32)
    )
