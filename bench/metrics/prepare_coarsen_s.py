"""Seconds ``prepare()`` spent on every heavy-edge-matching coarsening level in Band-k (``repro.obs`` timer ``prepare/phase.reorder.coarsen``)."""


def read(run):
    # like the operator-call spans, read where the run was traced on a device
    ms = run.obs.get("prepare/phase.reorder.coarsen_ms")
    return None if ms is None or run.trace is None else ms / 1e3
