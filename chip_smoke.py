"""Smoke-run the SpMV library compiled on a TPU, at the paper's published sizes.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the sharded thermal2 phase only

One chip drives the library through the entry points a user calls:

* ``prepare(format="auto")`` on the suite's ecology1 (1,000,000 rows) and
  thermal2 (1,227,664 rows) analogues at ``scale=1``; both route to CSR-k.
  f32 ``op(x)`` and ``op(X)`` with X of shape [n, 8], then bf16 and int8
  value streams;
* the irregular backends, each at the largest n its whole-x kernel accepts:
  ``powerlaw_zipf`` (segsum), ``pareto_rows`` (SELL-C-σ) and
  ``stencil_fringe`` (DIA + CSR hybrid);
* a ``ServeEngine`` answering coalesced requests, each bit-identical to a
  direct call of an operator prepared the same way;
* ``cg`` on ecology1 to a relative residual of 1e-4;
* a probe of what the MXU does with an f32 matmul at default precision.

Every result is compared with a float64 scipy CSR product under a tolerance
stated next to it.  Every operator must run compiled (``interpret`` False,
``tpu_custom_call`` in its lowered program).  Each phase prints one JSON
line; the last line is ``{"ok": true, "device": {...}}``.  The script exits
non-zero, and prints no such line, when JAX finds no TPU or any check fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

from repro.util.platform import configure_compile_cache  # noqa: E402

configure_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import scipy.sparse as sp  # noqa: E402

from repro.configs.spmv_suite import (  # noqa: E402
    SUITE, pareto_rows, powerlaw_zipf, stencil_fringe,
)
from repro.core.solvers import cg  # noqa: E402
from repro.core.spmv import prepare  # noqa: E402
from repro.kernels.gather import WHOLE_X_MAX_COLS  # noqa: E402

U32 = 2.0 ** -24   # f32 unit roundoff

#: Relative-L2 bounds of the compressed value streams: the documented
#: acceptance bounds of ``prepare(value_dtype=...)`` (core/spmv.py), i.e.
#: the error of rounding each stored value to bf16 or to int8 with one
#: scale per 128 slots.  Accumulation is f32 in every case.
L2_BOUND = {"bf16": 5e-3, "int8": 2.5e-2}


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def emit(**rec):
    print(json.dumps(rec, default=float), flush=True)


def peak_hbm_gb():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


def scipy_csr(A):
    return sp.csr_matrix(
        (np.asarray(A.vals, np.float64), np.asarray(A.col_idx), np.asarray(A.row_ptr)),
        shape=A.shape,
    )


def f32_row_bound(M):
    """Per-row f32 tolerance relative to Σ_j |a_ij x_j|.

    Each of a row's k products and additions rounds by at most 2^-24 of
    the running absolute sum (the γ_k summation bound), plus a fixed
    allowance for the kernels' 128-slot group adds and one-hot passes.
    """
    return (int(np.diff(M.indptr).max(initial=0)) + 32) * U32


def errors(y, M, x):
    """(max per-row error / Σ|a x|, relative L2 error) against float64."""
    y = np.asarray(y, np.float64)
    x = np.asarray(x, np.float64)
    ref = M @ x
    absrow = abs(M) @ np.abs(x)
    row = np.abs(y - ref) / np.maximum(absrow, 1e-30)
    l2 = np.linalg.norm(y - ref) / max(np.linalg.norm(ref), 1e-30)
    return float(row.max()), float(l2)


def assert_compiled(op, x):
    check(op.interpret is False, "operator resolved to interpret mode on a TPU")
    fn, operands = op.launch()
    text = jax.jit(fn).lower(operands, x).as_text()
    check("tpu_custom_call" in text, "lowered operator holds no Mosaic kernel")


def timed(fn, x, reps=3):
    """(y, compile seconds, steady ms per call); every call ends in
    ``block_until_ready``.  Compile seconds are the first call's time less
    one steady call."""
    t0 = time.perf_counter()
    y = jax.block_until_ready(fn(x))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        y = jax.block_until_ready(fn(x))
    steady = (time.perf_counter() - t0) / reps
    return y, first - steady, steady * 1e3


def run_op_phase(name, op, M, x, *, value_dtype="f32", backend=None):
    """One operator call: compiled check, timing, error vs float64."""
    if backend is not None:
        check(op.backend == backend, f"{name}: routed to {op.backend}, expected {backend}")
    assert_compiled(op, x)
    y, compile_s, ms = timed(op.apply_original, x)
    check(y.shape == (M.shape[0],) + x.shape[1:], f"{name}: output shape {y.shape}")
    check(bool(jnp.all(jnp.isfinite(y))), f"{name}: non-finite output")
    row_err, l2 = errors(y, M, x)
    if value_dtype == "f32":
        tol, metric, err = f32_row_bound(M), "max_row_rel", row_err
    else:
        tol, metric, err = L2_BOUND[value_dtype], "rel_l2", l2
    emit(phase=name, backend=op.backend, value_dtype=value_dtype,
         shape=list(M.shape), nnz=int(M.nnz), B=int(x.shape[1]) if x.ndim == 2 else 1,
         interpret=op.interpret, compile_s=compile_s, call_ms=ms,
         max_row_rel_err=row_err, rel_l2_err=l2, metric=metric, tol=tol,
         peak_hbm_gb=peak_hbm_gb())
    check(err <= tol, f"{name}: {metric} {err:.3e} > {tol:.3e}")
    return y


def precision_probe():
    """What does the MXU do with f32 operands, per dot precision?

    x times an identity inside a Pallas kernel, as a plain and as a
    transposed-RHS contraction: any error is operand rounding.  The kernels
    do not depend on the answer — every operand they feed the MXU is exact
    in bf16 — but it says what an f32 dot at default precision costs.
    """
    from jax.experimental import pallas as pl

    x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 128)), jnp.float32)
    eye = jnp.eye(128, dtype=jnp.float32)
    out = {}
    for name, prec in (("default", None), ("highest", jax.lax.Precision.HIGHEST)):
        for form, dims in (("nn", ((1,), (0,))), ("nt", ((1,), (1,)))):
            def kernel(x_ref, e_ref, o_ref, prec=prec, dims=dims):
                o_ref[...] = jax.lax.dot_general(
                    x_ref[...], e_ref[...], (dims, ((), ())), precision=prec,
                    preferred_element_type=jnp.float32,
                )

            y = pl.pallas_call(
                kernel, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32)
            )(x, eye)
            out[f"max_rel_err_{name}_{form}"] = float(jnp.max(jnp.abs(y - x) / jnp.abs(x)))
    emit(phase="precision_probe", **out)


def suite_phase(entry_id, rng):
    """A Table 2 matrix at published N: f32 [n] and [n, 8], bf16, int8.

    The bf16 and int8 operators are prepared from the f32 operator's
    reordered CSR view (``reorder="natural"``), so Band-k runs once.
    """
    entry = next(e for e in SUITE if e.id == entry_id)
    t0 = time.perf_counter()
    A = entry.build(1)
    op = prepare(A, device="tpu_v5e", format="auto")
    tb = op.tile_buckets
    emit(phase=f"{entry.name}/prepare", shape=list(A.shape), nnz=int(A.nnz),
         build_and_prepare_s=time.perf_counter() - t0, tiles=tb.num_tiles,
         rows_per_tile=tb.rows_per_tile, window=tb.window,
         bucket_slots=list(tb.bucket_slots()))
    M = scipy_csr(A)
    x = jnp.asarray(rng.standard_normal(A.shape[1]), jnp.float32)
    X = jnp.asarray(rng.standard_normal((A.shape[1], 8)), jnp.float32)
    run_op_phase(f"{entry.name}/f32/B1", op, M, x, backend="csrk")
    run_op_phase(f"{entry.name}/f32/B8", op, M, X, backend="csrk")
    Mr = scipy_csr(op.csr)
    for vd in ("bf16", "int8"):
        opv = prepare(op.csr, device="tpu_v5e", format="auto",
                      reorder="natural", value_dtype=vd)
        run_op_phase(f"{entry.name}/{vd}/B1", opv, Mr, x, value_dtype=vd,
                     backend="csrk")
    return A, M, op


def irregular_phase(rng):
    cases = (
        ("powerlaw_zipf", powerlaw_zipf(WHOLE_X_MAX_COLS["segsum"]), "segsum"),
        ("pareto_rows", pareto_rows(WHOLE_X_MAX_COLS["sellcs"]), "sellcs"),
        ("stencil_fringe",
         stencil_fringe(side=int(np.sqrt(WHOLE_X_MAX_COLS["diahybrid"]))),
         "diahybrid"),
    )
    for name, A, backend in cases:
        M = scipy_csr(A)
        op = prepare(A, device="tpu_v5e", format="auto")
        x = jnp.asarray(rng.standard_normal(A.shape[1]), jnp.float32)
        run_op_phase(f"{name}/f32/B1", op, M, x, backend=backend)
        X = jnp.asarray(rng.standard_normal((A.shape[1], 8)), jnp.float32)
        run_op_phase(f"{name}/f32/B8", op, M, X, backend=backend)


def serve_phase(rng):
    from repro.configs.spmv_suite import grid_laplacian_2d
    from repro.serve import ServeEngine

    mats = {"grid": grid_laplacian_2d(256, 256), "pareto": pareto_rows(8192)}
    eng = ServeEngine(max_batch=8, device="tpu_v5e", format="auto")
    for mid, A in mats.items():
        eng.add_matrix(mid, A)
    reqs = []
    t0 = time.perf_counter()
    for i in range(24):
        mid = ("grid", "pareto")[i % 2]
        n = mats[mid].shape[1]
        width = 1 + i % 3
        shape = (n,) if width == 1 else (n, width)
        x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        reqs.append((mid, x, eng.submit(mid, x)))
    eng.drain()
    wall = time.perf_counter() - t0
    direct = {mid: prepare(A, device="tpu_v5e", format="auto", spmm_width=8)
              for mid, A in mats.items()}
    # the engine answers in each operator's own (reordered) index space
    ref = {mid: scipy_csr(op.csr if op.backend == "csrk" else mats[mid])
           for mid, op in direct.items()}
    worst = 0.0
    for mid, x, fut in reqs:
        got = np.asarray(fut.result())
        check(np.array_equal(got, np.asarray(direct[mid](x))),
              f"serve: engine result for {mid} differs from the direct call")
        row_err, _ = errors(got, ref[mid], x)
        worst = max(worst, row_err / f32_row_bound(ref[mid]))
    emit(phase="serve", requests=len(reqs), wall_s=wall,
         backends=sorted(op.backend for op in direct.values()),
         bit_identical_to_direct=True, worst_err_over_tol=worst)
    check(worst <= 1.0, "serve: result outside the f32 bound")


def cg_phase(A, M, op, rng):
    tol = 1e-4
    x_true = rng.standard_normal(A.shape[1])
    b = jnp.asarray(M @ x_true, jnp.float32)
    t0 = time.perf_counter()
    res = cg(op.apply_original, b, tol=tol, maxiter=300)
    x = np.asarray(jax.block_until_ready(res.x), np.float64)
    wall = time.perf_counter() - t0
    bn = np.asarray(b, np.float64)
    true_res = float(np.linalg.norm(bn - M @ x) / np.linalg.norm(bn))
    emit(phase="ecology1/cg", iters=int(res.iters), wall_s=wall,
         recursive_rel_residual=float(res.residual) / float(np.linalg.norm(bn)),
         true_rel_residual_f64=true_res, tol=tol)
    # the f32 recurrence and the float64 residual of its iterate agree to a
    # small multiple of the tolerance
    check(int(res.iters) < 300 and true_res <= 2 * tol,
          f"cg: true residual {true_res:.2e} after {int(res.iters)} iterations")


def sharded_phase(rng):
    from jax.sharding import Mesh

    entry = next(e for e in SUITE if e.id == 11)
    A = entry.build(1)
    M = scipy_csr(A)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    t0 = time.perf_counter()
    sop = prepare(A, device="tpu_v5e", format="auto", mesh=mesh, x_strategy="halo")
    one = prepare(A, device="tpu_v5e", format="auto")
    prep_s = time.perf_counter() - t0
    check(sop.backend == "csrk" and sop.x_strategy == "halo",
          f"sharded: {sop.backend}/{sop.x_strategy}, expected csrk/halo")
    # every stacked tile array lives one shard per device
    for key, arr in sop.shard_arrays.items():
        devs = [s.device for s in arr.addressable_shards]
        check(len(set(devs)) == 4 and all(s.data.shape[0] == 1 for s in arr.addressable_shards),
              f"sharded: {key} is not split one shard per device")
    x = jnp.asarray(rng.standard_normal(A.shape[1]), jnp.float32)
    X = jnp.asarray(rng.standard_normal((A.shape[1], 8)), jnp.float32)
    text = sop.lower(x).compile().as_text()
    check("tpu_custom_call" in text, "sharded: no Mosaic kernel in the program")
    check("collective-permute" in text, "sharded: no halo collective-permute")
    for label, xin in (("B1", x), ("B8", X)):
        y, compile_s, ms = timed(sop.apply_original, xin)
        y1 = jax.block_until_ready(one.apply_original(xin))
        same = bool(np.array_equal(np.asarray(y), np.asarray(y1)))
        row_err, l2 = errors(y, M, xin)
        tol = f32_row_bound(M)
        emit(phase=f"thermal2/mesh4/{label}", backend=sop.backend,
             x_strategy=sop.x_strategy, overlap=sop.overlap, halo=sop.halo,
             shards=sop.num_shards, shape=list(A.shape), prepare_s=prep_s,
             compile_s=compile_s, call_ms=ms, bit_identical_to_one_chip=same,
             max_row_rel_err=row_err, rel_l2_err=l2, tol=tol)
        check(same, "sharded: result differs from the one-chip operator")
        check(row_err <= tol, f"sharded: max_row_rel {row_err:.3e} > {tol:.3e}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 2
    rng = np.random.default_rng(args.seed)
    try:
        if args.chips == 4:
            sharded_phase(rng)
        else:
            precision_probe()
            A, M, op = suite_phase(8, rng)
            cg_phase(A, M, op, rng)
            suite_phase(11, rng)
            irregular_phase(rng)
            serve_phase(rng)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
