"""Device milliseconds per SpMV call under the program spans ``repro.permute_in`` and ``repro.permute_out``, from the trace."""
from bench import attribution


def read(run):
    a, calls = attribution.of(run), run.host.get("calls")
    ms = None if a is None or not calls else a.device("repro.permute_in", "repro.permute_out")
    return None if ms is None else ms / calls * 1e3
