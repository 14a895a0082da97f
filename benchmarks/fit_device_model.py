"""Fit the constant-time tuner's device model from measured sweeps.

  python -m benchmarks.fit_device_model [--quick] [--scale N] \
      [--out device_model.json] [--name tpu_v5e]

Runs the paper's Sec. 4 calibration protocol end to end on this machine:

1. for each Table-2 suite matrix, sweep (SSRS, SRS) over the candidate set
   and keep the wall-clock optimum (the same sweep benchmarks/tuning_model.py
   prints, here with a ``--quick`` subset);
2. fit ``size = a − b·ln(rdensity)`` for SSRS and SRS independently via
   :func:`repro.core.tuner.fit_log_model`;
3. sweep the Pallas x-gather chunk width on a representative matrix and keep
   the fastest;
4. write the fitted constants as JSON in the exact shape
   :func:`repro.core.tuner.load_fitted_device_model` consumes:

      {"tpu_v5e": {"ssrs": [a, b], "srs": [a, b], "gather_chunk": g}}

Point the tuner at the file with ``REPRO_DEVICE_MODEL=device_model.json`` or
``tuner.use_device_model(tuner.load_fitted_device_model(path))`` — a missing
or stale file silently falls back to the hand-set :data:`tuner.TPU_V5E`.
"""
from __future__ import annotations

import argparse
import json

import numpy as np
import jax.numpy as jnp

from benchmarks.common import time_fn
from repro.configs.spmv_suite import SUITE
from repro.core import tuner
from repro.core.formats import build_csrk, tiles_from_csrk
from repro.core.ordering import bandk
from repro.kernels import ref
from repro.kernels.spmv_csrk import spmv_csrk_tiles_pallas

QUICK_IDS = (1, 9, 12, 16)      # spans rdensity ≈ 2.8 … 71.5
GATHER_CHUNKS = (128, 256, 512, 1024)


def sweep_optima(scale: int, ids=None) -> tuple:
    """Per-matrix wall-clock optimum over the (SSRS, SRS) candidate grid.

    Returns (rdensities, opt_ssrs, opt_srs) numpy arrays.
    """
    rds, opt_ssrs, opt_srs = [], [], []
    for entry in SUITE:
        if ids is not None and entry.id not in ids:
            continue
        A = entry.build(scale)
        A = A.symmetric_permute(bandk(A))
        x = jnp.asarray(
            np.random.default_rng(0).standard_normal(A.n), jnp.float32
        )
        best = (None, float("inf"))
        for ssrs in tuner.GPU_SWEEP:
            for srs in tuner.GPU_SWEEP:
                if ssrs * srs > max(A.m // 4, 8):
                    continue
                tiles = tiles_from_csrk(build_csrk(A, srs=srs, ssrs=ssrs, k=3))
                t = time_fn(lambda v, ti=tiles: ref.spmv_csrk_tiles(ti, v), x,
                            warmup=1, iters=3)
                if t < best[1]:
                    best = ((ssrs, srs), t)
        rds.append(A.rdensity)
        opt_ssrs.append(best[0][0])
        opt_srs.append(best[0][1])
        print(f"# {entry.name}: rdensity={A.rdensity:.2f} opt={best[0]}")
    return np.asarray(rds), np.asarray(opt_ssrs), np.asarray(opt_srs)


def sweep_gather_chunk(scale: int) -> int:
    """Time the actual Pallas kernel (the only consumer of gather_chunk)
    across chunk widths on the smallest suite matrix; interpret mode makes
    this Python-bound, so keep the matrix tiny and iters minimal — on a real
    TPU the same sweep measures the hardware gather/one-hot tradeoff."""
    entry = min(SUITE, key=lambda e: e.paper_n)
    A = entry.build(scale)
    A = A.symmetric_permute(bandk(A))
    params = tuner.tune_tpu(A.rdensity)
    tiles = tiles_from_csrk(
        build_csrk(A, srs=params.srs, ssrs=params.ssrs, k=3)
    )
    n = tiles.shape[1]
    W = tiles.window
    # mirror ops._pad_x_to_blocks: every (win_block, win_block+1) pair valid
    xp = jnp.pad(
        jnp.asarray(np.random.default_rng(0).standard_normal(n), jnp.float32),
        (0, (-(-n // W) + 1) * W - n),
    )
    best = (GATHER_CHUNKS[0], float("inf"))
    for chunk in GATHER_CHUNKS:
        t = time_fn(
            lambda v, c=chunk: spmv_csrk_tiles_pallas(
                tiles.vals, tiles.local_col, tiles.local_row,
                tiles.win_block, tiles.col_blocks, v, tiles.val_scale,
                rows_per_tile=tiles.rows_per_tile, window=W,
                gather_chunk=c,
            ),
            xp, warmup=1, iters=2,
        )
        print(f"# gather_chunk={chunk}: {t * 1e3:.1f} ms")
        if t < best[1]:
            best = (chunk, t)
    return best[0]


def run(scale: int = 1024, quick: bool = False, out: str = "device_model.json",
        name: str = "tpu_v5e", chunk_sweep: bool = True) -> dict:
    rds, ssrs, srs = sweep_optima(scale, ids=QUICK_IDS if quick else None)
    a1, b1 = tuner.fit_log_model(rds, ssrs)
    a2, b2 = tuner.fit_log_model(rds, srs)
    gc = (sweep_gather_chunk(max(scale, 1024)) if chunk_sweep
          else tuner.TPU_V5E.gather_chunk)
    model = {name: {"ssrs": [a1, b1], "srs": [a2, b2], "gather_chunk": gc}}
    with open(out, "w") as fh:
        json.dump(model, fh, indent=2)
    print(f"SSRS = round({a1:.3f} - {b1:.3f} * ln(rdensity))")
    print(f"SRS  = round({a2:.3f} - {b2:.3f} * ln(rdensity))")
    print(f"# wrote {out}; activate with REPRO_DEVICE_MODEL={out}")
    return model


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="4-matrix subset, skip the gather-chunk sweep")
    ap.add_argument("--scale", type=int, default=1024,
                    help="suite down-scale divisor (paper N / scale)")
    ap.add_argument("--out", default="device_model.json")
    ap.add_argument("--name", default="tpu_v5e",
                    help="device entry name in the JSON / DEVICES table")
    args = ap.parse_args()
    run(scale=args.scale, quick=args.quick, out=args.out, name=args.name,
        chunk_sweep=not args.quick)


if __name__ == "__main__":
    main()
