"""Host milliseconds per ``apply_original`` call until it returns, before blocking (benchmark clock)."""


def read(run):
    return run.host.get("dispatch_ms")
