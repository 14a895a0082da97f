"""Device milliseconds per CG iteration under the program spans ``repro.permute_in`` and ``repro.permute_out``, from the trace."""
from bench import attribution


def read(run):
    a, iters = attribution.of(run), run.host.get("iterations")
    ms = None if a is None or not iters else a.device("repro.permute_in", "repro.permute_out")
    return None if ms is None else ms / iters * 1e3
