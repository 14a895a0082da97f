"""Readings that set the limits of the multigrid cell's ``correct``: the program's and its control's.

    python3 bench/mg_control.py --workload hpcg104.mgpcg --side control --seconds 3 --seeds 1 2 3
    python3 bench/mg_control.py --workload hpcg104.mgpcg --side program --seconds 3 --seeds 1 2 3

As ``bench/control.py``, for the ``mgpcg`` driver, which that file cannot
drive: its control must replace the V-cycle as well as the product and the
solver.  Each seed builds the cell's matrix and goes through the cell's own
driver and checks, at the cell's own size, after a short window.
``--side program`` prepares the library's operator and hierarchy.  ``--side
control`` runs the reference V-cycle (``bench.mg_reference.vcycle``) and
PCG (:func:`plain_pcg`) under ``jax.numpy`` on the device, with every
product's input rounded to bfloat16 (:func:`bf16_matvec`; HPCG's values
are exact in bfloat16): the nearest precision below the configuration's
f32, which the checks have to reject.  No library code runs.  One JSON line per seed, then a summary line with the largest and
smallest reading of each number.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SIDES = ("program", "control")


def round_bf16(x):
    """f32 ``x`` rounded to the nearest bfloat16 (ties to even), in integer
    operations: inside a jitted program XLA:TPU may treat an f32 → bf16 →
    f32 round trip as excess precision and skip it, and did in this cell."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def bf16_matvec(mat):
    """``x -> A x`` with x rounded to bfloat16 (:func:`round_bf16`) and the
    products summed in f32; the values, exact in bfloat16, as they are."""
    import jax
    import jax.numpy as jnp

    vals, cols = jnp.asarray(mat.data), jnp.asarray(mat.indices)
    rows, m = jnp.asarray(mat.row_ids()), mat.shape[0]

    def apply(x):
        return jax.ops.segment_sum(vals * round_bf16(x)[cols], rows, num_segments=m)

    return apply


class ControlOperator:
    """:func:`bf16_matvec` in the place of a prepared operator."""

    def __init__(self, mat):
        self.apply_original = bf16_matvec(mat)


def plain_pcg(matvec, b, *, tol: float, maxiter: int, precond):
    """Textbook preconditioned CG from x0 = 0 to ``|r| <= tol |b|`` (jax.numpy),
    its divisions guarded as ``repro.core.solvers.cg`` guards them, so a
    stalled bfloat16 solve ends finite."""
    import jax
    import jax.numpy as jnp

    from bench.reference import Solve

    tol2 = tol ** 2 * jnp.vdot(b, b)
    z = precond(b)

    def cond(s):
        return jnp.logical_and(jnp.vdot(s[1], s[1]) > tol2, s[4] < maxiter)

    def body(s):
        x, r, p, rz, k = s
        ap = matvec(p)
        alpha = rz / jnp.maximum(jnp.vdot(p, ap), 1e-30)
        x = x + alpha * p
        r = r - alpha * ap
        z = precond(r)
        rz_new = jnp.vdot(r, z)
        return x, r, z + (rz_new / jnp.maximum(rz, 1e-30)) * p, rz_new, k + 1

    x, _, _, _, k = jax.lax.while_loop(
        cond, body, (jnp.zeros_like(b), b, z, jnp.vdot(b, z), 0))
    return Solve(x, k)


def control_vcycle(mat, traffic: dict):
    """The reference V-cycle over bfloat16 products of the cell's levels."""
    import jax.numpy as jnp

    from bench import mg_reference
    from bench.matrices import hpcg_27pt
    from bench.reference import to_scipy

    L = int(traffic["levels"])
    gs = mg_reference.grids(mat.grid, L)
    mats = [mat] + [hpcg_27pt.build(*g) for g in gs[1:]]
    levels = [mg_reference.Level(bf16_matvec(m),
                                 jnp.asarray(to_scipy(m).diagonal(), jnp.float32),
                                 jnp.asarray(mg_reference.f2c(g)) if l + 1 < L else None)
              for l, (m, g) in enumerate(zip(mats, gs))]

    def vcycle(r):
        return mg_reference.vcycle(levels, r, nu=int(traffic["nu"]),
                                   omega=float(traffic["omega"]),
                                   add_at=lambda x, i, v: x.at[i].add(v))

    return vcycle


def readings(p: dict, side: str, seeds, seconds: float):
    """Yield ``(seed, {name: (value, limit)})`` for each seed."""
    from bench import harness, matrices
    from bench.drivers import mgpcg
    from bench.reference import Reference

    for seed in seeds:
        mat = matrices.generate(p["config"]["matrix"], seed)
        if side == "program":
            op, _ = harness.prepare_operator(mat, p["config"])
            driver = mgpcg.Driver(op, mat, p["traffic"], seed)
        else:
            driver = mgpcg.Driver(ControlOperator(mat), mat, p["traffic"], seed,
                                  solver=plain_pcg, vcycle=control_vcycle(mat, p["traffic"]))
        driver.warm()
        driver.window(seconds)
        checks, _ = driver.checks(Reference(mat), driver.answers())
        yield seed, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--side", choices=SIDES, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, "bench", ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import harness

    dev = jax.devices()[0]
    p = harness.plan(args.workload)
    seen = {}
    t0 = time.perf_counter()
    for seed, checks in readings(p, args.side, args.seeds, args.seconds):
        for name, (value, limit) in checks.items():
            seen.setdefault(name, []).append(value)
        print(json.dumps({"seed": seed, "side": args.side, "workload": args.workload,
                          "checks": {k: {"value": v, "limit": lim}
                                     for k, (v, lim) in checks.items()}}), flush=True)
    print(json.dumps({"side": args.side, "workload": args.workload, "seeds": len(args.seeds),
                      "max": {k: max(v) for k, v in seen.items()},
                      "min": {k: min(v) for k, v in seen.items()},
                      "seconds": time.perf_counter() - t0,
                      "device": {"platform": dev.platform, "kind": dev.device_kind}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
