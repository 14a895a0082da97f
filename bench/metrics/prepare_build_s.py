"""Seconds ``prepare()`` spent building the kernel's tiles (``repro.obs`` timer ``prepare/phase.tile_build``)."""


def read(run):
    ms = run.obs.get("prepare/phase.tile_build_ms")
    return None if ms is None else ms / 1e3
