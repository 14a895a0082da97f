"""Mean iterations per CG solve in the window, as ``cg`` returns them."""


def read(run):
    return run.host.get("cg_iters")
