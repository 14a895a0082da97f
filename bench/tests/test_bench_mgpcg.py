"""The multigrid cell on the CPU at a tiny size: its generator, its checks
(the program passes; the bfloat16 control, a skipped level, a changed ν or
ω fail) and its readers.

Runs go through :func:`bench.harness.run_cell` on a 4³ grid with 2
levels (4³ on SELL-C-σ → 2³ on CSR-k), the cell's own 50 iterations and
smoother.  A traced run is left out: on the CPU the trace of interpret-mode
kernels takes minutes to read, and its device metrics are absent anyway.
"""
import dataclasses
import time

import numpy as np
import pytest

from bench import harness, matrices, mg_control, scope_time
from bench.matrices import hpcg_27pt
from bench.trace_reduce import Event, Line, Plane, TraceSummary
from repro.core import multigrid

SEED = 2**31 + 29
CELL = "hpcg104.mgpcg"
US = 1000.0  # ns


def tiny():
    p = harness.plan(CELL)
    p["config"]["matrix"]["params"].update(nx=4, ny=4, nz=4)
    p["traffic"]["levels"] = 2
    return p


def run(p, trace=False, seconds=0.3):
    return harness.run_cell(p, SEED, seconds, trace, time.perf_counter())


@pytest.mark.parametrize("grid", [(8, 8, 8), (6, 4, 2), (3, 3, 3)])
def test_generator_matches_the_library(grid):
    from repro.configs.spmv_suite import grid_laplacian_3d

    lib = grid_laplacian_3d(*grid, stencil=27)
    mine = hpcg_27pt.build(*grid)
    assert mine.grid == grid and mine.shape == tuple(lib.shape)
    np.testing.assert_array_equal(mine.indptr, np.asarray(lib.row_ptr))
    np.testing.assert_array_equal(mine.indices, np.asarray(lib.col_idx))
    np.testing.assert_array_equal(mine.data, np.asarray(lib.vals))
    assert mine.nnz == hpcg_27pt.nnz(grid)
    assert mine.data.dtype == np.float32 and mine.indices.dtype == np.int32


def test_configuration_is_hpcgs_published_grid():
    p = harness.plan(CELL)
    cfg = p["config"]
    grid = tuple(cfg["matrix"]["params"][k] for k in ("nx", "ny", "nz"))
    assert list(grid) == cfg["published"]["grid"] and cfg["reduced"] == []
    n = int(np.prod(grid))
    assert cfg["rows"] == n == cfg["published"]["rows"]
    assert cfg["nnz"] == hpcg_27pt.nnz(grid) == cfg["published"]["nnz"]
    # the traffic's levels halve the grid down to a whole coarsest grid
    assert all(g % (1 << (p["traffic"]["levels"] - 1)) == 0 for g in grid)
    # the seed draws no values: HPCG's are fixed
    a, b = matrices.generate(cfg["matrix"] | {"params": {"nx": 4, "ny": 4, "nz": 4}}, 1), \
        matrices.generate(cfg["matrix"] | {"params": {"nx": 4, "ny": 4, "nz": 4}}, 2)
    np.testing.assert_array_equal(a.data, b.data)


def test_sound_run_is_correct():
    r = run(tiny())
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    for name in ("residual", "vcycle_error"):
        assert 0 <= r["checks"][name]["value"] <= r["checks"][name]["limit"], name
    assert set(r["metrics"]) == {"setup_s", "solve_s"}


def test_control_fails_and_program_passes():
    """The bfloat16 control reads above both limits, the program below."""
    p = tiny()
    for side, above in (("control", True), ("program", False)):
        for seed, checks in mg_control.readings(p, side, [3], 0.2):
            for name, (value, limit) in checks.items():
                assert (value > limit) == above, (side, seed, name, value, limit)


def test_reference_pcg_converges_and_its_vcycle_is_symmetric():
    """The float64 reference is a valid preconditioned CG: the V-cycle is
    symmetric positive definite, and 20 iterations reach 1e-9."""
    from bench.mg_reference import MgReference

    mat = hpcg_27pt.build(8, 8, 8)
    ref = MgReference(mat, 3)
    rng = np.random.default_rng(0)
    u, v = rng.standard_normal((2, mat.shape[0]))
    Mu, Mv = (ref.vcycle(w, nu=2, omega=0.8) for w in (u, v))
    assert abs(Mu @ v - u @ Mv) <= 1e-12 * np.linalg.norm(Mu) * np.linalg.norm(v)
    assert Mu @ u > 0
    b = ref.A[0] @ u
    x = ref.pcg(b, iters=20, nu=2, omega=0.8)
    assert np.linalg.norm(b - ref.A[0] @ x) < 1e-9 * np.linalg.norm(b)


FAULTS = {
    # the coarsest level left out: the one above it only smooths
    "skipped_level": lambda h: dataclasses.replace(
        h, levels=h.levels[:-2] + (dataclasses.replace(h.levels[-2], f2c=None),)),
    "nu_3": lambda h: dataclasses.replace(h, nu=3),
    "omega_0.7": lambda h: dataclasses.replace(h, omega=0.7),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_different_vcycle_is_not_correct(fault, monkeypatch):
    """The V-cycle check alone rejects each: the solves are left out, since
    PCG converges under any of these preconditioners."""
    from bench.drivers import mgpcg
    from bench.reference import Reference

    good = multigrid.hierarchy
    monkeypatch.setattr(multigrid, "hierarchy",
                        lambda *a, **kw: FAULTS[fault](good(*a, **kw)))
    p = tiny()
    mat = matrices.generate(p["config"]["matrix"], SEED)
    op, _ = harness.prepare_operator(mat, p["config"])
    driver = mgpcg.Driver(op, mat, p["traffic"], SEED)
    checks, failed = driver.checks(Reference(mat), driver.answers())
    value, limit = checks["vcycle_error"]
    assert value > limit and failed == 1


def test_new_readers_find_nothing_without_a_trace():
    run_ = harness.Run(host={"solves": 2, "iterations": 100, "vcycles": 102}, obs={},
                       trace=None, mat=None, peaks=None)
    for name in ("vcycle_ms.mgpcg", "coarse_pct.mgpcg", "mg_roofline.mgpcg"):
        assert harness.load_reader(name)(run_) is None, name


def test_roofline_counts_the_products_of_one_iteration():
    grid = (104, 104, 104)
    mat = hpcg_27pt.GridCsr(np.zeros(1), np.zeros(1), np.zeros(1), (1, 1), grid=grid)
    t = TraceSummary(chips=1, window_s=10.0, busy_s=5.0, kernel_s=4.0, op_s={}, gaps=[])
    run_ = harness.Run(host={"iterations": 100}, obs={}, trace=t, mat=mat,
                       peaks={"hbm_bytes_per_s": 1e9})

    def product(n):
        m = n ** 3
        return 8 * (3 * n - 2) ** 3 + 4 * (m + 1) + 8 * m

    # nu = 2: 1 + 4 products on 104³, 4 on 52³ and 26³, 1 on the coarsest 13³
    nbytes = 5 * product(104) + 4 * product(52) + 4 * product(26) + product(13)
    assert harness.load_reader("mg_roofline.mgpcg")(run_) == pytest.approx(
        nbytes / 1e9 / (5.0 / 100) * 100)


def ev(name, start_us, dur_us, **stats):
    return Event(name, start_us * US, dur_us * US, tuple(stats.items()))


def test_span_time_counts_every_span_in_the_scope_path():
    body = "jit(solve)/repro.cg/while/body"
    vc = f"{body}/repro.mg.vcycle"
    planes = [
        Plane("/host:CPU", (Line("python", (ev("bench.window", 0, 100),)),)),
        Plane("/device:TPU:0", (Line("XLA Ops", (
            ev("while.1", 0, 100, tf_op="jit(solve)/repro.cg/while"),
            ev("spmv_csrk.1", 10, 20, tf_op=f"{vc}/repro.mg.level0/repro.mg.smooth/"
                                             "repro.apply_original/pallas_call"),
            ev("fusion.1", 30, 10, tf_op=f"{vc}/repro.mg.level0/repro.mg.restrict/sub"),
            ev("spmv_sellcs.1", 40, 30, tf_op=f"{vc}/repro.mg.level1/repro.mg.smooth/"
                                               "repro.apply_original/pallas_call"),
            # fused across two levels: only the scopes both share
            ev("fusion.2", 70, 10, tf_op=f"{vc}/repro.mg.level1/add;{vc}/repro.mg.level0/mul"),
            ev("fusion.3", 80, 10, tf_op=f"{body}/repro.apply_original/gather"),
        )),)),
    ]
    s = scope_time.under(planes)
    assert s["repro.mg.vcycle"] == pytest.approx(70e-6)
    assert s["repro.mg.level0"] == pytest.approx(30e-6)
    assert s["repro.mg.level1"] == pytest.approx(30e-6)
    assert s["repro.apply_original"] == pytest.approx(60e-6)
    assert s["repro.cg"] == pytest.approx(100e-6)
    run_ = harness.Run(host={"vcycles": 2}, obs={}, trace=object(), mat=None, peaks=None)
    run_.scope_s = s
    assert harness.load_reader("vcycle_ms.mgpcg")(run_) == pytest.approx(35e-3)
    assert harness.load_reader("coarse_pct.mgpcg")(run_) == pytest.approx(30 / 70 * 100)
