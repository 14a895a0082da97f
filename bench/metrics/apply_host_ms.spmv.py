"""Host milliseconds per call inside the program span ``repro.apply_original``, from the trace: the program's own reading of ``dispatch_ms.spmv``."""
from bench import attribution

SPAN = "repro.apply_original"


def read(run):
    a = attribution.of(run)
    if a is None or not a.host_calls.get(SPAN):
        return None
    return a.host_s[SPAN] / a.host_calls[SPAN] * 1e3
