"""Irregular-matrix backends: speculative segmented-sum CSR + DIA/CSR hybrid.

Three layers under test:
  * containers (``SegSumCSR`` / ``DIAHybridMatrix``): round-trips, chunk/
    diagonal geometry, hand-computed carry and remainder cases;
  * kernels vs oracles: ``ops.spmv_segsum`` / ``ops.spmv_diahybrid`` must
    match ``ref.spmv_segsum`` / ``ref.spmv_diahybrid`` for [n] and [n, B]
    inputs across value dtypes, up to the order of the f32 additions
    (tests/_close.py) — bit for bit where both sum in the same order or the
    values make every sum exact;
  * routing: the adversarial families auto-select the new backends while
    every pre-existing suite matrix keeps its prior decision, and the mesh
    path declines the non-tile backends into the recorded CSR-2 fallback.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax.numpy as jnp

from repro.configs.spmv_suite import (
    load_adversarial,
    load_suite,
    powerlaw_zipf,
    stencil_fringe,
)
from repro.core.spmv import prepare
from repro.kernels import ops, ref
from repro.sparse import (
    CSRMatrix,
    DIA_FRACTION_MIN,
    SEGSUM_ROW_SKEW_MIN,
    compute_stats,
    dense_diagonals,
    diahybrid_from_csr,
    segsum_from_csr,
    select_format,
)

from _close import assert_reordered_sum_close

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _csr(dense: np.ndarray) -> CSRMatrix:
    return CSRMatrix.fromdense(np.asarray(dense, np.float32))


# --- segmented-sum container -------------------------------------------------


def test_segsum_todense_roundtrip():
    A = powerlaw_zipf(2048)
    seg = segsum_from_csr(A, chunk_slots=128)
    np.testing.assert_array_equal(
        np.asarray(seg.todense()), np.asarray(A.todense())
    )
    assert seg.nnz == A.nnz
    assert seg.chunk_slots % 128 == 0
    # equal-nnz chunking: every chunk but the last is completely full
    assert seg.num_chunks == -(-A.nnz // seg.chunk_slots)


def test_segsum_hand_computed_three_chunk_carry():
    """One row spanning 3 chunks: the speculative partials are wrong in every
    chunk and only the carry/patch scatter makes them right.  All values are
    small integers, so f32 arithmetic is exact and the check is literal
    equality against hand-computed numbers."""
    m, n = 4, 512
    dense = np.zeros((m, n), np.float32)
    dense[0, :300] = 1.0                       # row 0: 300 nnz -> 3 chunks
    dense[2, 10], dense[2, 400] = 2.0, 3.0
    dense[3, [0, 100, 200, 300, 511]] = 1.0
    A = _csr(dense)
    seg = segsum_from_csr(A, chunk_slots=128)
    assert seg.num_chunks == 3 and seg.chunk_slots == 128
    # row 0 owns the first segment of chunks 0, 1 AND 2 (the carried row)
    sr = np.asarray(seg.seg_row)
    assert sr[0, 0] == 0 and sr[1, 0] == 0 and sr[2, 0] == 0

    x = jnp.asarray((np.arange(n) % 7 + 1).astype(np.float32))
    # sum_{j<300} x[j] = 42 full 1..7 cycles (28 each) + (1..6) = 1197
    want = np.array([1197.0, 0.0, 14.0, 17.0], np.float32)
    y_ref = ref.spmv_segsum(seg, x)
    y_ker = ops.spmv_segsum(seg, x, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_ref), want)
    np.testing.assert_array_equal(np.asarray(y_ker), want)


def test_segsum_handles_empty_rows_and_trailing_padding():
    dense = np.zeros((13, 17), np.float32)     # ragged, mostly-empty
    dense[3, [0, 5, 12]] = [1.0, -2.0, 4.0]
    dense[11, 2] = -2.0
    A = _csr(dense)
    seg = segsum_from_csr(A)
    x = np.arange(17, dtype=np.float32)
    y = ops.spmv_segsum(seg, jnp.asarray(x), interpret=True)
    np.testing.assert_array_equal(np.asarray(y), dense @ x)


# --- DIA/CSR hybrid container ------------------------------------------------


def test_dense_diagonals_extraction_policy():
    """Occupancy is measured against the m plane slots a DIA row costs, so a
    fully-occupied short corner diagonal can never earn a plane row."""
    n = 32
    dense = np.zeros((n, n), np.float32)
    np.fill_diagonal(dense, 2.0)                       # 32/32 = 1.0
    dense[np.arange(n - 3), np.arange(3, n)] = 1.0     # +3: 29/32 ≈ 0.91
    dense[np.arange(5, n), np.arange(n - 5)] = 1.0     # -5: 27/32 ≈ 0.84
    dense[0, n - 1] = 9.0                              # +31: 1/32
    A = _csr(dense)
    assert list(dense_diagonals(A)) == [0, 3]
    # the -5 diagonal clears a lowered threshold; the singleton never does
    assert list(dense_diagonals(A, occupancy=0.8)) == [-5, 0, 3]
    assert len(dense_diagonals(A, occupancy=1.1)) == 0


def test_diahybrid_hand_computed_offsets_and_remainder():
    """Sub-, main- and super-diagonal plane + a single CSR remainder entry,
    with integer values: results must equal the hand computation exactly."""
    m = 8
    dense = np.zeros((m, m), np.float32)
    np.fill_diagonal(dense, 2.0)                            # offset 0
    dense[np.arange(2, m), np.arange(m - 2)] = 1.0          # offset -2
    dense[np.arange(m - 2), np.arange(2, m)] = 3.0          # offset +2
    dense[0, 7] = 5.0                                       # remainder
    A = _csr(dense)
    # at m=8 the ±2 diagonals fill 6/8 = 0.75 of a plane row — extract them
    # with an explicit threshold; the (0,7) singleton stays remainder
    mat = diahybrid_from_csr(A, occupancy=0.7)
    assert mat.offsets == (-2, 0, 2)
    assert mat.remainder.nnz == 1
    assert mat.diag_nnz == A.nnz - 1
    np.testing.assert_array_equal(np.asarray(mat.todense()), dense)

    x = np.arange(1.0, m + 1.0, dtype=np.float32)
    want = dense @ x                                        # exact: small ints
    y_ref = ref.spmv_diahybrid(mat, jnp.asarray(x))
    y_ker = ops.spmv_diahybrid(mat, jnp.asarray(x), interpret=True)
    np.testing.assert_array_equal(np.asarray(y_ref), want)
    np.testing.assert_array_equal(np.asarray(y_ker), want)


def test_diahybrid_pure_plane_and_pure_remainder_degenerate():
    # all-diagonal matrix: empty remainder branch must not perturb the plane
    d = np.diag(np.arange(1.0, 9.0)).astype(np.float32)
    mat = diahybrid_from_csr(_csr(d))
    assert mat.remainder.nnz == 0
    x = np.ones(8, np.float32)
    np.testing.assert_array_equal(
        np.asarray(ops.spmv_diahybrid(mat, jnp.asarray(x), interpret=True)),
        d @ x,
    )
    # no dense diagonal at all: everything rides the remainder
    s = np.zeros((16, 16), np.float32)
    s[0, :7] = 1.0
    mat2 = diahybrid_from_csr(_csr(s))
    assert len(mat2.offsets) == 0 and mat2.remainder.nnz == 7
    x2 = np.arange(16, dtype=np.float32)
    np.testing.assert_array_equal(
        np.asarray(ops.spmv_diahybrid(mat2, jnp.asarray(x2), interpret=True)),
        s @ x2,
    )


def test_diahybrid_rejects_int8_values():
    A = _csr(np.eye(8, dtype=np.float32))
    with pytest.raises(ValueError):
        diahybrid_from_csr(A, value_dtype="int8")
    with pytest.raises(ValueError):
        prepare(A, format="diahybrid", value_dtype="int8")


# --- kernel vs oracle on the adversarial families ---------------------------


@pytest.mark.parametrize("value_dtype", ["f32", "bf16", "int8"])
def test_segsum_kernel_bitexact_vs_oracle(rng, value_dtype):
    A = powerlaw_zipf(2048)
    seg = segsum_from_csr(A, chunk_slots=256, value_dtype=value_dtype)
    x = jnp.asarray(rng.standard_normal(A.n).astype(np.float32))
    X = jnp.asarray(rng.standard_normal((A.n, 3)).astype(np.float32))
    for xin in (x, X):
        y_ker = ops.spmv_segsum(seg, xin, interpret=True)
        y_ref = ref.spmv_segsum(seg, xin)
        assert y_ker.shape == y_ref.shape == (A.m,) + xin.shape[1:]
        assert_reordered_sum_close(y_ker, y_ref)
    if value_dtype == "f32":
        yd = np.asarray(A.todense()) @ np.asarray(x)
        np.testing.assert_allclose(
            np.asarray(ops.spmv_segsum(seg, x, interpret=True)),
            yd, rtol=2e-4, atol=2e-4,
        )


@pytest.mark.parametrize("value_dtype", ["f32", "bf16"])
def test_diahybrid_kernel_bitexact_vs_oracle(rng, value_dtype):
    A = stencil_fringe(side=48)
    mat = diahybrid_from_csr(A, value_dtype=value_dtype)
    assert len(mat.offsets) >= 5                  # the 9-point diagonals
    x = jnp.asarray(rng.standard_normal(A.n).astype(np.float32))
    X = jnp.asarray(rng.standard_normal((A.n, 3)).astype(np.float32))
    for xin in (x, X):
        y_ker = ops.spmv_diahybrid(mat, xin, interpret=True)
        y_ref = ref.spmv_diahybrid(mat, xin)
        assert y_ker.shape == y_ref.shape == (A.m,) + xin.shape[1:]
        np.testing.assert_array_equal(np.asarray(y_ker), np.asarray(y_ref))
    if value_dtype == "f32":
        yd = np.asarray(A.todense()) @ np.asarray(x)
        np.testing.assert_allclose(
            np.asarray(ops.spmv_diahybrid(mat, x, interpret=True)),
            yd, rtol=2e-4, atol=2e-4,
        )


def test_diahybrid_rectangular_and_small_tiles(rng):
    """Non-square shape + a row_tile that forces a multi-block grid.

    Two products per row: whether XLA fuses them into one FMA differs
    between the kernel body and the oracle, hence the reordering bound."""
    dense = np.zeros((130, 200), np.float32)
    dense[np.arange(130), np.arange(130)] = rng.standard_normal(130)
    dense[np.arange(130), np.arange(130) + 40] = rng.standard_normal(130)
    dense[5, [0, 199]] = 1.0
    mat = diahybrid_from_csr(_csr(dense))
    assert set(mat.offsets) == {0, 40}
    x = jnp.asarray(rng.standard_normal(200).astype(np.float32))
    y_ker = ops.spmv_diahybrid(mat, x, row_tile=64, interpret=True)
    y_ref = ref.spmv_diahybrid(mat, x)
    assert_reordered_sum_close(y_ker, y_ref)


# --- routing: adversarial families in, suite decisions unchanged ------------


def test_adversarial_families_route_to_new_backends():
    mats = load_adversarial()
    st_p = compute_stats(mats["powerlaw_zipf"])
    st_s = compute_stats(mats["stencil_fringe"])
    assert st_p.row_skew >= SEGSUM_ROW_SKEW_MIN and not st_p.is_regular
    assert st_s.diag_fraction >= DIA_FRACTION_MIN and not st_s.is_regular
    assert select_format(st_p, "tpu_v5e") == "segsum"
    assert select_format(st_s, "tpu_v5e") == "diahybrid"


def test_suite_routing_decisions_unchanged():
    """The extended stats must not move any Table 2 analogue off its prior
    backend — segsum/diahybrid only capture the new adversarial regimes."""
    for name, A in load_suite(scale=512).items():
        sel = select_format(compute_stats(A), "tpu_v5e")
        assert sel in ("csrk", "sellcs"), (name, sel)


def test_prepare_auto_powerlaw_executes_segsum(rng):
    A = powerlaw_zipf(4096)
    op = prepare(A, device="tpu_v5e", format="auto")
    assert op.backend == "segsum"
    assert op.segsum is not None and op.dia is None
    x = jnp.asarray(rng.standard_normal(A.n).astype(np.float32))
    X = jnp.asarray(rng.standard_normal((A.n, 2)).astype(np.float32))
    seg = op.segsum
    assert_reordered_sum_close(op(x), ref.spmv_segsum(seg, x))
    assert_reordered_sum_close(op(X), ref.spmv_segsum(seg, X))
    # identity permutation: apply_original is the same computation
    np.testing.assert_array_equal(
        np.asarray(op.apply_original(x)), np.asarray(op(x))
    )
    assert op.modeled_bytes() > 0 and 0.0 <= op.overhead_fraction() < 1.0


def test_prepare_auto_stencil_executes_diahybrid(rng):
    A = stencil_fringe(side=64)
    op = prepare(A, device="tpu_v5e", format="auto")
    assert op.backend == "diahybrid"
    assert op.dia is not None and op.segsum is None
    assert op.value_dtype in ("f32", "bf16")       # int8 candidates excluded
    x = jnp.asarray(rng.standard_normal(A.n).astype(np.float32))
    X = jnp.asarray(rng.standard_normal((A.n, 2)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(op(x)), np.asarray(ref.spmv_diahybrid(op.dia, x))
    )
    np.testing.assert_array_equal(
        np.asarray(op(X)), np.asarray(ref.spmv_diahybrid(op.dia, X))
    )
    np.testing.assert_array_equal(
        np.asarray(op.apply_original(x)), np.asarray(op(x))
    )


def test_prepare_forced_new_backends_on_tame_matrix(rng):
    """Forcing the formats on a matrix that would not route to them must
    still execute correctly (same contract as forced sellcs)."""
    from repro.configs.spmv_suite import grid_laplacian_2d

    A = grid_laplacian_2d(12, 12)
    x = rng.standard_normal(A.n).astype(np.float32)
    yd = np.asarray(A.todense()) @ x
    for fmt in ("segsum", "diahybrid"):
        op = prepare(A, format=fmt)
        assert op.backend == fmt
        np.testing.assert_allclose(
            np.asarray(op(jnp.asarray(x))), yd, rtol=2e-4, atol=1e-4
        )
        with pytest.raises(AttributeError):
            _ = op.csr                              # CSR-k-only property


# --- mesh path: declined tile partitioning, recorded fallback ----------------


def test_mesh_declines_segsum_to_recorded_csr_fallback():
    """segsum/diahybrid carry no shardable tile view: prepare(mesh=...) must
    fall to the CSR-2 raw-row fallback (like cpu devices do), keep per-shard
    registry decisions in shard_backends, and stay numerically correct."""
    script = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core.spmv import prepare
from repro.configs.spmv_suite import powerlaw_zipf, stencil_fringe

mesh = Mesh(np.asarray(jax.devices()).reshape(4, 1), ('data', 'model'))
rng = np.random.default_rng(0)
for A, fmt in ((powerlaw_zipf(2048), 'segsum'),
               (stencil_fringe(side=48), 'diahybrid')):
    op = prepare(A, format=fmt, value_dtype='f32', mesh=mesh)
    assert op.backend == fmt, op.backend
    assert len(op.shard_backends) == 4, op.shard_backends
    x = jnp.asarray(rng.standard_normal(A.n), jnp.float32)
    yd = np.asarray(A.todense()) @ np.asarray(x)
    err = float(jnp.abs(op(x) - yd).max())
    assert err < 1e-3, (fmt, err)
print('OK')
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=560, env=env,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "OK" in out.stdout
