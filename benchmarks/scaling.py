"""Paper Fig. 10 analogue: scalability study.

The paper scales OpenMP threads on Rome/Ice Lake; the JAX analogue scales
device count for the distributed SpMV.  Everything runs in this process over
meshes built from the first d of ``jax.devices()`` (d = 1, 2, 4, 8, up to
the devices present) — no child process, so nothing competes for a chip this
process already holds.  On a CPU host, present several devices by setting
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before starting.
Speedups are normalised to 1 device, geometric-mean across the suite subset.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from benchmarks.common import emit, time_fn
from repro.configs.spmv_suite import SUITE
from repro.core.distributed import dist_spmv_halo, shard_csr
from repro.core.ordering import bandk


def run(device_counts=None) -> list:
    devices = jax.devices()
    if device_counts is None:
        device_counts = [d for d in (1, 2, 4, 8) if d <= len(devices)]
    mats = {}
    for entry in SUITE:
        if entry.id in (6, 8, 11):
            A = entry.build(128)
            mats[entry.name] = A.symmetric_permute(bandk(A))
    rng = np.random.default_rng(0)
    xs = {k: jnp.asarray(rng.standard_normal(A.m), jnp.float32)
          for k, A in mats.items()}

    times = {}
    for d in device_counts:
        mesh = Mesh(np.asarray(devices[:d]).reshape(d, 1), ("data", "model"))
        times[d] = {}
        for name, A in mats.items():
            S = shard_csr(A, d)
            times[d][name] = time_fn(
                lambda v: dist_spmv_halo(S, v, mesh), xs[name],
                warmup=3, iters=10,
            )

    rows = []
    base = times[device_counts[0]]
    for d in device_counts:
        speedups = [base[k] / times[d][k] for k in base]
        geo = float(np.exp(np.mean(np.log(speedups))))
        rows.append({"devices": d, "geomean_speedup": round(geo, 3)})
    emit(rows, ["devices", "geomean_speedup"])
    return rows


if __name__ == "__main__":
    run()
