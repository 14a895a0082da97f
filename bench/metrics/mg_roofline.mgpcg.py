"""Share of the HBM roofline one PCG iteration reaches, over the device's busy time per iteration.

Bytes are the benchmark's own count of the sparse products one iteration
does by definition, whatever implements them: a plain CSR product at level l
moves 8·nnz_l + 4·(m_l + 1) + 8·m_l bytes (values and column indices, row
pointers, x read once and y written once), with nnz_l the 27-point count on
the level's grid.  One iteration has one outer product and one V-cycle:
2·nu products at every level above the coarsest (nu − 1 pre-smoothing
products after the one from zero, one residual, nu post-smoothing) and
nu − 1 on the coarsest, with ``levels`` and ``nu`` from the traffic file.
The least time is those bytes at the device's peak HBM bandwidth
(``peaks.json``); the share is that over the device-busy time per
iteration, so the V-cycle before each solve's loop, the vector work and the
glue count against it.
"""
import json
import os

from bench.matrices import hpcg_27pt

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "traffic", "mgpcg.json")


def iteration_bytes(grid, levels: int, nu: int) -> int:
    total = 0
    for l in range(levels):
        g = [s >> l for s in grid]
        m = g[0] * g[1] * g[2]
        products = (2 * nu if l < levels - 1 else nu - 1) + (l == 0)
        total += products * (8 * hpcg_27pt.nnz(g) + 4 * (m + 1) + 8 * m)
    return total


def read(run):
    t, iters, peaks = run.trace, run.host.get("iterations"), run.peaks
    if t is None or not iters or peaks is None or t.busy_s <= 0:
        return None
    with open(TRAFFIC) as f:
        traffic = json.load(f)
    nbytes = iteration_bytes(run.mat.grid, int(traffic["levels"]), int(traffic["nu"]))
    return nbytes / peaks["hbm_bytes_per_s"] / (t.busy_s / iters) * 100
