"""Seconds ``prepare()`` spent on every weighted Cuthill-McKee ordering, with its pseudo-peripheral BFS in Band-k (``repro.obs`` timer ``prepare/phase.reorder.order``)."""


def read(run):
    # like the operator-call spans, read where the run was traced on a device
    ms = run.obs.get("prepare/phase.reorder.order_ms")
    return None if ms is None or run.trace is None else ms / 1e3
