"""Device seconds of the window under each program span anywhere in an
operation's scope path.

:mod:`bench.attribution` charges each moment of busy device time to the
innermost ``repro.*`` span only.  A span that contains others, such as
``repro.mg.vcycle`` around the levels, the smoother and the operator calls,
is read here instead: each operation's piece of the busy union (the
innermost operation covering a moment owns it, as there) counts toward every
span of its scope path (``attribution.scope_of``).  An operation with no
scope path counts toward none.  :func:`of` is what a reader calls; it is
computed once per run.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

from bench import attribution
from bench import trace_reduce as tr


def under(planes: Sequence[tr.Plane],
          named: Optional[Dict[str, Dict[str, str]]] = None) -> Optional[Dict[str, float]]:
    """span -> device seconds in the window under it, averaged over the chips;
    None without a window or a device."""
    found = tr.window_span(planes)
    devices = [p for p in planes if tr._DEVICE_PLANE.match(p.name)]
    if found is None or not devices:
        return None
    _, win = found
    lo, hi = win.start_ns, win.end_ns
    out: Dict[str, float] = {}
    for p in devices:
        ops = [e for ln in p.lines if ln.name == tr.OPS_LINE for e in ln.events
               if e.end_ns > lo and e.start_ns < hi]
        for i, sec in attribution.leaf_segments(ops, lo, hi).items():
            for span in set(attribution.scope_of(ops[i], (named or {}).get(p.name))):
                out[span] = out.get(span, 0.0) + sec / len(devices)
    return out


def of(run, trace_dir: Optional[str] = None) -> Optional[Dict[str, float]]:
    """:func:`under` of ``run``'s own trace, or None where the run was not
    traced on a device.  Kept on the run as ``run.scope_s``."""
    if not hasattr(run, "scope_s"):
        run.scope_s = None
        if trace_dir is None:
            from bench import harness

            trace_dir = harness.TRACE_DIR
        path = tr.find_xplane(trace_dir) if run.trace is not None else None
        if path is not None:
            run.scope_s = under(tr.load(path), attribution.op_names(path))
    return run.scope_s
