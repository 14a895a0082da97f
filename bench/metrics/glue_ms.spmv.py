"""Device milliseconds per SpMV call of every operation that is not a Pallas kernel, from the trace."""


def read(run):
    t, calls = run.trace, run.host.get("calls")
    if t is None or not calls or t.kernel_s <= 0:
        return None
    return t.glue_s / calls * 1e3
