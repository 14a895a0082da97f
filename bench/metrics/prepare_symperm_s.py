"""Seconds ``prepare()`` spent on the symmetric permute of the matrix and the post-reorder stats in the reorder phase (``repro.obs`` timer ``prepare/phase.reorder.symperm``)."""


def read(run):
    # like the operator-call spans, read where the run was traced on a device
    ms = run.obs.get("prepare/phase.reorder.symperm_ms")
    return None if ms is None or run.trace is None else ms / 1e3
