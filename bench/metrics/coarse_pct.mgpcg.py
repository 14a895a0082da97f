"""Share of the V-cycle's device time under the coarse levels' program spans ``repro.mg.level1`` and below, anywhere in an operation's scope path, from the trace.

The level spans do not nest (each holds its level's own work), so the
shares of the levels add up.
"""
from bench import scope_time

SPAN = "repro.mg.vcycle"
COARSE = ("repro.mg.level1", "repro.mg.level2", "repro.mg.level3")


def read(run):
    if run.trace is None:
        return None
    s = scope_time.of(run) or {}
    if not s.get(SPAN):
        return None
    return sum(s.get(c, 0.0) for c in COARSE) / s[SPAN] * 100
