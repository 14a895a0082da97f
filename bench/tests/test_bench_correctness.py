"""``correct`` on the CPU at a tiny size: sound runs pass; the control and
each fault the cells can have fail.

The runs go through :func:`bench.harness.run_cell` -- everything a chip run
does after its look for a chip -- with the library's timed path broken
underneath where a test plants a fault.  The cells run on one chip, so the
fault "exchange between chips left out" does not apply.
"""
import time

import jax.numpy as jnp
import pytest

from bench import control, harness
from repro.core import solvers
from repro.core.spmv import PreparedSpMV

SEED = 2**31 + 17


def tiny(workload, side=12):
    p = harness.plan(workload)
    p["config"]["matrix"]["params"].update(nx=side, ny=side + 1)
    return p


def run(p, trace=False, seconds=0.3):
    return harness.run_cell(p, SEED, seconds, trace, time.perf_counter())


@pytest.fixture(params=["ecology1.spmv", "ecology1.cg"])
def cell(request):
    return request.param


def test_sound_run_is_correct(cell):
    r = run(tiny(cell))
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    name = "row_error" if cell.endswith("spmv") else "residual"
    value, limit = r["checks"][name]["value"], r["checks"][name]["limit"]
    assert 0 <= value <= limit
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in tiny(cell)["end_to_end"]}


def test_traced_run_reports_host_metrics_only_off_chip():
    r = run(tiny("ecology1.spmv"), trace=True)
    assert r["correct"] is True
    # no TPU plane in a CPU trace: every device metric is left out, never 0
    assert set(r["metrics"]) == {"prepare_reorder_s", "prepare_build_s", "dispatch_ms.spmv"}
    assert "breakdown" not in r and "busy_s" not in r["device"]


def _broken_apply(fault):
    good = PreparedSpMV.apply_original

    def apply(self, x):
        y = good(self, x)
        if fault == "unchanged":
            return x
        if fault == "half_left_out":
            return y.at[y.shape[0] // 2:].set(0)
        return y.at[y.shape[0] // 3].add(1.0)      # one answer altered
    return apply


@pytest.mark.parametrize("fault", ["unchanged", "half_left_out", "altered"])
def test_fault_in_the_operator_is_not_correct(cell, fault, monkeypatch):
    monkeypatch.setattr(PreparedSpMV, "apply_original", _broken_apply(fault))
    r = run(tiny(cell))
    assert r["correct"] is False and r["failed"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_fault_in_the_solver_is_not_correct(fault, monkeypatch):
    good = solvers.cg

    def cg(matvec, b, **kw):
        res = good(matvec, b, **kw)
        if fault == "unchanged":
            return res._replace(x=jnp.zeros_like(b))
        return res._replace(x=res.x.at[5].add(1.0))

    monkeypatch.setattr(solvers, "cg", cg)
    r = run(tiny("ecology1.cg"))
    assert r["correct"] is False and r["failed"] == r["attempted"]


def test_control_fails_and_program_passes(cell):
    """The bf16 control, and the values alone in bf16, read above every
    limit; the program below it."""
    p = tiny(cell, side=16)
    for side, above in (("control", True), ("values_bf16", True), ("program", False)):
        for seed, checks in control.readings(p, side, [3, 2**31 + 3], 0.2):
            for value, limit in checks.values():
                assert (value > limit) == above, (side, seed, value, limit)


def test_x_split_short_of_a_term_fails_the_row_limit():
    """The kernels carry f32 x as three bfloat16 terms; a product that drops
    the third (x kept to 16 bits of mantissa) reads above ``row_error``'s limit."""
    import numpy as np

    from bench import drivers, matrices
    from bench.reference import Reference

    p = tiny("ecology1.spmv", side=32)
    mat = matrices.generate(p["config"]["matrix"], SEED)
    ref = Reference(mat)
    x = drivers.rng(SEED, 0).standard_normal(mat.shape[1]).astype(np.float32)
    x2 = (x.view(np.uint32) & np.uint32(0xFFFFFF00)).view(np.float32)
    y = ref.matvec(x2)
    assert ref.row_error(x, ref.matvec(x)) == 0
    assert ref.row_error(x, y) > p["traffic"]["checks"]["row_error"]
