"""Run one cell of ``BENCHMARK.json`` on the machine's TPU.

    python3 bench/run.py --workload ecology1.spmv --seed 7 --seconds 30 --trace 0

Earlier lines of standard output give the operator's tile geometry and the
compilations counted inside the window (there should be none); the last line
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared with the reference beside its limit.  The same numbers are
the last lines of standard error.

Exits non-zero, and prints no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the device kind has no row in
``bench/peaks.json``.  JAX's compilation cache is ``$JAX_COMPILATION_CACHE_DIR``
when set, else ``bench/.jax_cache`` in this checkout.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, "bench", ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    return harness.main(args, T0)


if __name__ == "__main__":
    sys.exit(main())
