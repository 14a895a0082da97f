"""Share of the HBM roofline one SpMV call reaches, over the device's busy time per call.

Bytes are the benchmark's own count of a plain CSR product of its own matrix,
whatever implements the product: values and column indices (8 B per
nonzero), row pointers (4 (m + 1)), x read once (4 n) and y written once
(4 m); the vector is one column.  The least time is those bytes at
the device's peak HBM bandwidth (``peaks.json``); the share is that over the
device-busy time per call, so glue around the kernels counts against it.
"""


def read(run):
    t, calls, peaks = run.trace, run.host.get("calls"), run.peaks
    if t is None or not calls or peaks is None or t.busy_s <= 0:
        return None
    m, n = run.mat.shape
    nbytes = 8 * run.mat.nnz + 4 * (m + 1) + 4 * (n + m)
    return nbytes / peaks["hbm_bytes_per_s"] / (t.busy_s / calls) * 100
