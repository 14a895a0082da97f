"""Readings that set the limits of ``correct``: the program's and its controls'.

    python3 bench/control.py --workload ecology1.spmv --side control --seconds 3 --seeds 1 2 3
    python3 bench/control.py --workload ecology1.spmv --side program --seconds 3 --seeds 1 2 3

Each seed builds the cell's matrix and goes through the cell's own traffic
driver and checks, at the cell's own size, after a short window.
``--side program`` prepares the library's operator for each seed (the
matrix's values come from the seed).  ``--side control`` puts the reference
in the program's place, computed in bfloat16 (``reference.control_matvec``;
a CG cell also swaps the library's ``cg`` for ``reference.plain_cg``): no
library code runs.  ``--side values_bf16`` is the same with only the matrix
values rounded to bfloat16.  One JSON line per seed, then a summary line
with the largest and smallest reading of each number.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


SIDES = ("program", "control", "values_bf16")


class ControlOperator:
    """The bfloat16 reference product in the place of a prepared operator."""

    def __init__(self, mat, round_x: bool = True):
        from bench.reference import control_matvec

        self.apply_original = control_matvec(mat, round_x=round_x)


def readings(p: dict, side: str, seeds, seconds: float):
    """Yield ``(seed, {name: (value, limit)})`` for each seed."""
    from bench import drivers, harness, matrices
    from bench.reference import Reference, plain_cg

    for seed in seeds:
        mat = matrices.generate(p["config"]["matrix"], seed)
        if side == "program":
            op, _ = harness.prepare_operator(mat, p["config"])
            solver = None
        else:
            op, solver = ControlOperator(mat, round_x=side == "control"), plain_cg
        driver = drivers.make_driver(op, mat, p["traffic"], seed, solver=solver)
        del op
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
