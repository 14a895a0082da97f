"""Seconds ``prepare()`` spent in Band-k reordering (``repro.obs`` timer ``prepare/phase.reorder``)."""


def read(run):
    ms = run.obs.get("prepare/phase.reorder_ms")
    return None if ms is None else ms / 1e3
