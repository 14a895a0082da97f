"""Device milliseconds per V-cycle under the program span ``repro.mg.vcycle``, anywhere in an operation's scope path, from the trace."""
from bench import scope_time

SPAN = "repro.mg.vcycle"


def read(run):
    if run.trace is None or not run.host.get("vcycles"):
        return None
    s = (scope_time.of(run) or {}).get(SPAN)
    return None if s is None else s / run.host["vcycles"] * 1e3
