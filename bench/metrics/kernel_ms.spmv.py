"""Device milliseconds of Pallas kernels per SpMV call, from the trace."""


def read(run):
    t, calls = run.trace, run.host.get("calls")
    if t is None or not calls or t.kernel_s <= 0:
        return None
    return t.kernel_s / calls * 1e3
