"""Device milliseconds of Pallas kernels per CG iteration, from the trace."""


def read(run):
    t, iters = run.trace, run.host.get("iterations")
    if t is None or not iters or t.kernel_s <= 0:
        return None
    return t.kernel_s / iters * 1e3
