"""Band-k ordering, RCM, and the constant-time tuning model (paper Sec. 4)."""
import numpy as np
import pytest
try:  # hypothesis is a dev-only dependency (requirements-dev.txt)
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - minimal installs
    from _hypothesis_fallback import given, settings, st

from repro.core.formats import CSRMatrix
from repro.core.ordering import bandk, bandwidth, rcm, graph_from_csr, coarsen
from repro.core import tuner
from repro.configs.spmv_suite import grid_laplacian_2d, road_graph


def test_rcm_reduces_bandwidth_on_shuffled_grid(rng):
    A = grid_laplacian_2d(24, 24)
    perm = rng.permutation(A.m)
    shuffled = A.symmetric_permute(perm)
    bw0 = bandwidth(shuffled)
    bw_rcm = bandwidth(shuffled.symmetric_permute(rcm(shuffled)))
    assert bw_rcm < bw0 / 4, (bw0, bw_rcm)


def test_bandk_reduces_bandwidth_on_shuffled_graph(rng):
    A = road_graph(1024, seed=9)
    perm = rng.permutation(A.m)
    shuffled = A.symmetric_permute(perm)
    bw0 = bandwidth(shuffled)
    bw_bk = bandwidth(shuffled.symmetric_permute(bandk(shuffled, k=3)))
    # paper Sec 2.2: Band-k is slightly wider than RCM but still band-limiting
    assert bw_bk < 0.7 * bw0, (bw0, bw_bk)
    bw_rcm = bandwidth(shuffled.symmetric_permute(rcm(shuffled)))
    assert bw_bk < 6 * max(bw_rcm, 1), (bw_bk, bw_rcm)


def test_bandk_is_permutation(rng):
    A = road_graph(512, seed=4)
    perm = bandk(A, k=3)
    assert sorted(perm.tolist()) == list(range(A.m))


def test_coarsening_shrinks_and_conserves_weight():
    A = grid_laplacian_2d(16, 16)
    g = graph_from_csr(A)
    gc, f2c = coarsen(g)
    assert gc.n < g.n
    assert np.isclose(gc.node_w.sum(), g.node_w.sum())
    assert f2c.max() == gc.n - 1


# --- paper Sec. 4 formulas, verbatim checks --------------------------------

def test_volta_formula_values():
    # rdensity=1 → ln=0 → SSRS=⌊8.900⌉=9, SRS=⌊10.146⌉=10
    p = tuner.tune_volta(1.0)
    assert (p.ssrs, p.srs) == (9, 10)
    assert not p.use_inner_parallel


def test_ampere_formula_values():
    p = tuner.tune_ampere(1.0)
    assert (p.ssrs, p.srs) == (9, 21)  # ⌊9.175⌉=9, ⌊20.500⌉ rounds half-up → 21


def test_ampere_case2_srs_x4():
    rd = 10.0
    base_ssrs, base_srs = tuner.AMPERE.base(rd)
    p = tuner.tune_ampere(rd)
    assert p.ssrs == base_ssrs
    assert p.srs == base_srs * 4
    assert p.use_inner_parallel


def test_inner_parallel_threshold_is_8():
    """Paper: intra-row parallelism pays off at rdensity ≥ 8."""
    assert not tuner.tune_tpu(7.9).use_inner_parallel
    assert tuner.tune_tpu(8.0).use_inner_parallel


def test_tpu_rows_per_ssr_alignment():
    for rd in [1.0, 3.0, 7.9, 9.0, 20.0, 50.0, 100.0]:
        p = tuner.tune_tpu(rd)
        assert p.rows_per_ssr % 8 == 0, (rd, p)


def test_cpu_constant_srs_is_96():
    assert tuner.tune_cpu(5.0).srs == 96
    assert tuner.tune_cpu(5.0).k == 2


def test_sweep_sets_match_paper():
    assert tuner.GPU_SWEEP == [4, 6, 8, 12, 16, 24, 32, 48]
    assert tuner.CPU_SRS_SWEEP[0] == 8
    assert tuner.CPU_SRS_SWEEP[-1] == 3072


def test_fit_log_model_recovers_coefficients():
    a, b = 9.2, 1.3
    rd = np.asarray([2.0, 4.0, 8.0, 16.0, 32.0, 64.0])
    sizes = a - b * np.log(rd)
    ahat, bhat = tuner.fit_log_model(rd, sizes)
    assert abs(ahat - a) < 1e-6 and abs(bhat - b) < 1e-6


@settings(max_examples=50, deadline=None)
@given(rd=st.floats(1.0, 200.0))
def test_property_tuner_total_time_constant(rd):
    """Tuning is O(1): pure arithmetic, sizes positive and bounded."""
    for dev in ("volta", "ampere", "tpu_v5e", "cpu"):
        p = tuner.tune(rd, device=dev)
        assert p.ssrs >= 1 and p.srs >= 1
        assert p.rows_per_ssr < 1_000_000


@settings(max_examples=30, deadline=None)
@given(rd=st.floats(1.0, 200.0))
def test_property_denser_means_shorter_tiles(rd):
    """Monotonicity of the log model: base sizes shrink as density grows."""
    lo = tuner.TPU_V5E.base(rd)
    hi = tuner.TPU_V5E.base(rd * 2)
    assert hi[0] <= lo[0] and hi[1] <= lo[1]


# --- byte model, vectorized extents, measured-model loader -----------------

def test_tile_bytes_model_hand_computed():
    """Pin the model against arithmetic done by hand: 2 tiles of 4 rows,
    nnz_t = (8, 4) → 128 slots; max col span 131 → W = 256; so
    total = 2 · (128·12 + 2·256·4 + 4·4) = 7200, useful = 12 nnz · 12 B."""
    rp = np.asarray([0, 2, 4, 6, 8, 9, 10, 11, 12], np.int64)
    cmin = np.asarray([0, 1, 2, 3, 0, 1, 2, 3], np.int64)
    cmax = np.asarray([5, 6, 7, 130, 0, 1, 2, 3], np.int64)
    total, eff = tuner.tile_bytes_model(rp, cmin, cmax, 4)
    assert total == 7200
    assert eff == 144 / 7200


def test_tune_tpu_rows_monotone_in_density():
    """Denser → shorter tiles, end to end through rounding: the paper-ladder
    densities give strictly decreasing Pallas tile heights."""
    heights = [tuner.tune_tpu(rd).rows_per_ssr for rd in (1, 8, 16, 32, 64, 128)]
    assert heights == sorted(heights, reverse=True)
    assert heights[0] > heights[-1]
    assert all(h % 8 == 0 for h in heights)


def test_row_col_extents_matches_per_row_loop(rng):
    """reduceat vectorization == the historical loop, incl. empty rows."""
    m = 64
    lengths = rng.integers(0, 6, m)
    lengths[::7] = 0                     # plant empty rows
    rp = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int64)
    ci = rng.integers(0, 100, rp[-1]).astype(np.int64)
    cmin, cmax = tuner.row_col_extents(rp, ci, m)
    for i in range(m):
        s, t = rp[i], rp[i + 1]
        lo, hi = (ci[s:t].min(), ci[s:t].max()) if t > s else (0, 0)
        assert (cmin[i], cmax[i]) == (lo, hi), i


def test_row_col_extents_all_empty():
    cmin, cmax = tuner.row_col_extents(np.zeros(5, np.int64), np.empty(0), 4)
    assert cmin.tolist() == [0, 0, 0, 0] and cmax.tolist() == [0, 0, 0, 0]


def test_cpu_sweep_requires_row_ptr_and_scores_padded_slots():
    with pytest.raises(ValueError, match="row_ptr"):
        tuner.tune_cpu(5.0, constant_time=False)
    # uniform rows: every candidate scores total-nnz, tie → largest SRS
    rp = np.arange(5, dtype=np.int64)
    p = tuner.tune_cpu(1.0, constant_time=False, row_ptr=rp)
    assert p.k == 2 and p.ssrs == 1
    assert p.srs == tuner.CPU_SRS_SWEEP[-1]


def test_gather_chunk_plumbs_from_model_to_params():
    assert tuner.TuningParams(
        ssrs=1, srs=1, k=3, use_inner_parallel=False
    ).gather_chunk == 512
    assert tuner.tune_tpu(5.0).gather_chunk == tuner.TPU_V5E.gather_chunk


def test_load_fitted_device_model_roundtrip(tmp_path):
    import json

    path = tmp_path / "device_model.json"
    path.write_text(json.dumps({
        "tpu_v5e": {"ssrs": [12.0, 2.0], "srs": [30.0, 4.0],
                    "gather_chunk": 256},
    }))
    dm = tuner.load_fitted_device_model(str(path))
    assert (dm.ssrs_a, dm.ssrs_b, dm.srs_a, dm.srs_b) == (12.0, 2.0, 30.0, 4.0)
    assert dm.gather_chunk == 256
    try:
        tuner.use_device_model(dm)
        p = tuner.tune_tpu(1.0)   # ln(1)=0 → base sizes are the a's
        assert (p.ssrs, p.srs) == (12, 30)
        assert p.gather_chunk == 256
    finally:
        tuner.use_device_model(None)
    assert tuner.tune_tpu(1.0).gather_chunk == tuner.TPU_V5E.gather_chunk


def test_load_fitted_device_model_fallbacks(tmp_path):
    # missing file, absent entry and malformed JSON all fall back, silently
    assert tuner.load_fitted_device_model(str(tmp_path / "nope.json")) is tuner.TPU_V5E
    empty = tmp_path / "empty.json"
    empty.write_text("{}")
    assert tuner.load_fitted_device_model(str(empty)) is tuner.TPU_V5E
    bad = tmp_path / "bad.json"
    bad.write_text('{"tpu_v5e": {"ssrs": "oops"}}')
    assert tuner.load_fitted_device_model(str(bad)) is tuner.TPU_V5E
    # a device with no hand-set model has nothing to fall back to
    with pytest.raises(ValueError, match="no device model"):
        tuner.load_fitted_device_model(str(empty), name="tpu_v9")


def test_env_var_activates_fitted_model(tmp_path, monkeypatch):
    import json

    path = tmp_path / "device_model.json"
    path.write_text(json.dumps({
        "tpu_v5e": {"ssrs": [9.0, 1.0], "srs": [10.0, 1.0],
                    "gather_chunk": 1024},
    }))
    try:
        monkeypatch.setenv("REPRO_DEVICE_MODEL", str(path))
        tuner.use_device_model(None)   # force re-resolution of the env var
        assert tuner.tune_tpu(5.0).gather_chunk == 1024
    finally:
        monkeypatch.delenv("REPRO_DEVICE_MODEL", raising=False)
        tuner.use_device_model(None)


def test_prepare_gather_chunk_override(rng):
    import jax.numpy as jnp
    from repro.core.spmv import prepare
    from repro.kernels import ref
    from repro.configs.spmv_suite import grid_laplacian_2d

    A = grid_laplacian_2d(16, 16)
    x = jnp.asarray(rng.standard_normal(A.m), jnp.float32)
    op = prepare(A, device="tpu_v5e", reorder="bandk", gather_chunk=256)
    assert op.params.gather_chunk == 256
    err = float(np.abs(np.asarray(op.apply_original(x))
                       - np.asarray(ref.spmv_csr(A, x))).max())
    assert err < 1e-4


def test_adaptive_tuner_never_worse_and_correct(rng):
    """Beyond-paper variance-aware tuner: modeled kernel bytes ≤ the paper
    formula's, and the resulting operator stays exact."""
    import jax.numpy as jnp
    from repro.core.spmv import prepare
    from repro.core.tuner import tile_bytes_model
    from repro.configs.spmv_suite import grid_laplacian_2d
    from repro.kernels import ref

    A = grid_laplacian_2d(32, 32)
    x = jnp.asarray(rng.standard_normal(A.m), jnp.float32)
    base = prepare(A, device="tpu_v5e", reorder="bandk")
    adpt = prepare(A, device="tpu_v5e", reorder="bandk", adaptive=True)
    err = float(jnp.abs(adpt.apply_original(x) - ref.spmv_csr(A, x)).max())
    assert err < 1e-4

    def modeled(op):
        rp = np.asarray(op.csrk.row_ptr)
        ci = np.asarray(op.csrk.col_idx)
        cmin = np.empty(op.csrk.m, np.int64)
        cmax = np.empty(op.csrk.m, np.int64)
        for i in range(op.csrk.m):
            s, t = rp[i], rp[i + 1]
            cmin[i], cmax[i] = (ci[s:t].min(), ci[s:t].max()) if t > s else (0, 0)
        return tile_bytes_model(rp, cmin, cmax, op.params.rows_per_ssr)[0]

    assert modeled(adpt) <= modeled(base)
