"""Share of a full one-hot sweep the CSR-k kernel visits, in % (``repro.obs`` gauge ``prepare/csrk.onehot_share``).

It counts the device kernel's work, so it is reported only where a trace
saw the kernel run on a device, like the kernel's time.
"""


def read(run):
    if run.trace is None:
        return None
    return run.obs.get("prepare/csrk.onehot_share")
