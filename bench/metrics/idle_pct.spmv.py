"""Share of the SpMV window in which no operation ran on the device, from the trace."""


def read(run):
    t = run.trace
    return None if t is None else t.idle_share * 100
