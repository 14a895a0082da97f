"""Charge the window's host and device time to the program's own spans.

The library names its regions ``repro.*`` (``repro.obs.annotate``): a host
span (``TraceAnnotation``) around the Python that runs them, and, under
``jit``, a scope in the op_name of every device operation they emit.  This
module reads a trace already reduced to plain records by
:mod:`bench.trace_reduce` and returns, for the window (``bench.window``):

* host seconds per program span, total and self (less its program child
  spans), and how many times it ran, on the thread that opened the window;
* device seconds per innermost program span, split into kernel and glue by
  :func:`bench.trace_reduce.kernel_of`;
* idle device seconds per innermost program span open on the host.

Each operation's piece of the busy union (the innermost operation covering
a moment owns it, so a ``while`` keeps only its own time) finds its span in
the first of these ways that works:

1. **scope** — the innermost ``repro.*`` component of its op_name
   (:data:`SCOPE_STAT`, which a TPU trace keeps in the event's metadata:
   :func:`op_names` reads it from the file); a jitted program carries it;
2. **launch** — the innermost program span open on the window's thread when
   the host launched its module execution.  The launch is found by following
   the trace's flows (``_c`` of an event to the ``_p`` of the event that
   began it, through the events enclosing each on its thread) from the
   ``XLA Modules`` execution back to the window's thread, or by a launch id
   (``run_id``) that a host event there shares; eager launches have it;
3. **module** — the outermost program scope common to the scoped operations
   of its module: an operation the compiler added inside a jitted solve
   (a copy, the loop's own control) belongs to the solve;
4. **order** — where no module execution links to the host at all, the
   window's outermost ``PjitFunction`` launches are matched to the device's
   module executions in order, one stream per device.

An operation no way reaches has no span.  The attribution fails
(:class:`AttributionFailed`) where the launches and executions matched in
order differ in number, or where more than :data:`MAX_UNATTRIBUTED` of the
busy time finds no span.  A window without the operator call's span
(:data:`ROOT_SPAN`), as in a program from before the spans, gives None.

:func:`of` is what a per-layer metric reader calls: the attribution of the
run's own trace, computed once per run and logged on one line of standard
output.
"""
from __future__ import annotations

import bisect
import dataclasses
import heapq
import json
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace_reduce as tr

PREFIX = "repro."
#: The operator call's span: a window without it has nothing to attribute.
ROOT_SPAN = "repro.apply_original"
#: The stat holding a device operation's op_name (``;``-joined when fused).
SCOPE_STAT = "tf_op"
FLOW_OUT, FLOW_IN, LAUNCH_STAT = "_p", "_c", "run_id"
MODULES_LINE = "XLA Modules"
LAUNCH_EVENT = "PjitFunction("
#: Largest share of busy device time that may find no span.
MAX_UNATTRIBUTED = 0.01
#: Most flows followed back from a module execution to the window's thread.
MAX_HOPS = 8
NO_SPAN = ""


class AttributionFailed(RuntimeError):
    """The trace's device time cannot be charged to the program's spans."""


@dataclasses.dataclass
class Attribution:
    """Where the window's time went, by program span (seconds; device figures
    averaged over the chips traced)."""

    how: str                          # the ways used: "launch", "module", "order", "scope"
    host_s: Dict[str, float]          # span -> host seconds, total
    host_self_s: Dict[str, float]     # span -> host seconds less program child spans
    host_calls: Dict[str, int]        # span -> times it ran in the window
    device_s: Dict[str, float]        # innermost span -> device seconds ("" no span)
    kernel_s: Dict[str, float]        # innermost span -> of which Pallas kernels
    idle_s: Dict[str, float]          # innermost host span -> idle device seconds
    busy_s: float

    @property
    def unattributed_s(self) -> float:
        return self.device_s.get(NO_SPAN, 0.0)

    def device(self, *spans: str) -> Optional[float]:
        """Device seconds under any of ``spans``; None if none of them ran."""
        found = [self.device_s[s] for s in spans if s in self.device_s]
        return sum(found) if found else None

    def summary(self) -> dict:
        """Per call of each host span, and the device and idle seconds."""
        return {
            "how": self.how,
            "host_ms_per_call": {
                k: {"calls": self.host_calls[k],
                    "total": self.host_s[k] / self.host_calls[k] * 1e3,
                    "self": self.host_self_s[k] / self.host_calls[k] * 1e3}
                for k in sorted(self.host_s)},
            "device_s": dict(sorted(self.device_s.items(), key=lambda kv: -kv[1])),
            "kernel_s": {k: v for k, v in self.kernel_s.items() if v > 0},
            "idle_s": dict(sorted(self.idle_s.items(), key=lambda kv: -kv[1])),
        }


def _stat(e: tr.Event, key: str):
    for k, v in e.stats:
        if k == key:
            return v
    return None


def scope_path(text: Optional[str]) -> List[str]:
    """The ``repro.*`` components of an op_name, outermost first.  A fused
    operation lists one op_name per fused operation, ``;``-joined: it gets
    the components they all share."""
    if not isinstance(text, str) or PREFIX not in text:
        return []
    common = None
    for name in text.split(";"):
        parts = [c for c in (c.rstrip(":") for c in name.split("/")) if c.startswith(PREFIX)]
        common = parts if common is None else _shared(common, parts)
    return common


def _shared(a: List[str], b: List[str]) -> List[str]:
    """The common leading part of two scope paths."""
    k = 0
    while k < min(len(a), len(b)) and a[k] == b[k]:
        k += 1
    return a[:k]


def scope_of(e: tr.Event, named: Optional[Dict[str, str]] = None) -> List[str]:
    """An operation's program scope path: from its own stat, else from its
    metadata (``named``: event name -> op_name, as :func:`op_names` reads)."""
    text = _stat(e, SCOPE_STAT)
    if text is None and named:
        text = named.get(e.name)
    return scope_path(text)


def _covering(events: Sequence[tr.Event], starts: List[float], t: float, reach: int = 512):
    """The latest-starting event of ``events`` (sorted by start) that covers t,
    among the ``reach`` that start last before it."""
    i = bisect.bisect_right(starts, t)
    for e in events[max(i - reach, 0):i][::-1]:
        if t < e.end_ns:
            return e
    return None


class _Spans:
    """Program spans of one host line, for innermost-at-time lookups."""

    def __init__(self, events: Sequence[tr.Event]):
        self.spans = sorted((e for e in events if e.name.startswith(PREFIX)),
                            key=lambda e: (e.start_ns, -e.dur_ns))
        self.starts = [e.start_ns for e in self.spans]

    def at(self, t: float) -> str:
        e = _covering(self.spans, self.starts, t)
        return NO_SPAN if e is None else e.name


def host_times(spans: Sequence[tr.Event], lo: float, hi: float):
    """(total, self, calls) per span name, clipped to [lo, hi).  Spans on one
    thread nest, so a span's self time is its length less its children's."""
    total: Dict[str, float] = {}
    own: Dict[str, float] = {}
    calls: Dict[str, int] = {}
    stack: List[tr.Event] = []
    for e in sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns)):
        d = (min(e.end_ns, hi) - max(e.start_ns, lo)) * 1e-9
        if d <= 0:
            continue
        while stack and stack[-1].end_ns <= e.start_ns:
            stack.pop()
        if stack:
            own[stack[-1].name] -= d
        total[e.name] = total.get(e.name, 0.0) + d
        own[e.name] = own.get(e.name, 0.0) + d
        calls[e.name] = calls.get(e.name, 0) + 1
        stack.append(e)
    return total, own, calls


def leaf_segments(ops: Sequence[tr.Event], lo: float, hi: float):
    """Split the busy union of ``ops`` into (op index, seconds) pieces, each
    piece charged to the innermost (latest-starting) operation covering it,
    so an operation that contains others (a ``while``) keeps only its own
    time and the pieces sum to the busy union."""
    ivs = [(max(e.start_ns, lo), min(e.end_ns, hi), i) for i, e in enumerate(ops)]
    ivs = [iv for iv in ivs if iv[1] > iv[0]]
    edges = sorted({x for s, t, _ in ivs for x in (s, t)})
    by_start = sorted(ivs)
    heap: List[Tuple[float, float, int]] = []     # (-start, end, index)
    out: Dict[int, float] = {}
    j = 0
    for a, b in zip(edges, edges[1:]):
        while j < len(by_start) and by_start[j][0] <= a:
            s, t, i = by_start[j]
            heapq.heappush(heap, (-s, t, i))
            j += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:   # no op ends inside (a, b), so the live top covers all of it
            i = heap[0][2]
            out[i] = out.get(i, 0.0) + (b - a) * 1e-9
    return out


class _Flows:
    """The host side of the trace's flows, to find when the window's thread
    launched a module execution.  A flow is its id and its type (``_p`` with
    ``_pt`` where it starts, ``_c`` with ``_ct`` where it ends): ids of
    different types can be equal."""

    def __init__(self, planes: Sequence[tr.Plane], window_line: tr.Line):
        self.window_line = window_line
        self.made: Dict[tuple, List[Tuple[float, tr.Line, tr.Event]]] = {}
        self.by_run: Dict[object, Tuple[tr.Line, tr.Event]] = {}
        self.taking: Dict[int, Tuple[List[tr.Event], List[float]]] = {}
        for p in planes:
            if not p.name.startswith(tr._HOST_PLANE):
                continue
            for ln in p.lines:
                takers = []
                for e in ln.events:
                    if not e.stats:
                        continue
                    st = dict(e.stats)
                    if FLOW_OUT in st:
                        self.made.setdefault((st[FLOW_OUT], st.get(FLOW_OUT + "t")), []).append(
                            (e.start_ns, ln, e))
                    if FLOW_IN in st:
                        takers.append(e)
                    if LAUNCH_STAT in st:
                        self.by_run.setdefault(st[LAUNCH_STAT], (ln, e))
                takers.sort(key=lambda e: (e.start_ns, -e.dur_ns))
                self.taking[id(ln)] = (takers, [e.start_ns for e in takers])
        for starts in self.made.values():
            starts.sort(key=lambda x: x[0])

    def _origin(self, e: tr.Event) -> Optional[Tuple[tr.Line, tr.Event]]:
        """The event that began the flow ending at ``e``: of those with its id
        and type, the one that starts nearest before ``e`` (else nearest)."""
        st = dict(e.stats)
        found = self.made.get((st.get(FLOW_IN), st.get(FLOW_IN + "t")))
        if not found:
            return None
        i = bisect.bisect_right([x[0] for x in found], e.start_ns) - 1
        _, line, ev = found[i] if i >= 0 else found[0]
        return line, ev

    def launch_time(self, module: tr.Event) -> Optional[float]:
        """When the window's thread launched ``module``, or None."""
        at = self._origin(module) or self.by_run.get(_stat(module, LAUNCH_STAT))
        for _ in range(MAX_HOPS):
            if at is None:
                return None
            line, e = at
            if line is self.window_line:
                return e.start_ns
            if _stat(e, FLOW_IN) is None:      # the flow into the event this one runs in
                takers, starts = self.taking[id(line)]
                e = _covering(takers, starts, e.start_ns)
                if e is None:
                    return None
            at = self._origin(e)
        return None


def _outermost_launches(host_line: tr.Line, lo: float, hi: float) -> List[tr.Event]:
    launches = sorted((e for e in host_line.events if e.name.startswith(LAUNCH_EVENT)
                       and lo <= e.start_ns < hi), key=lambda e: (e.start_ns, -e.dur_ns))
    out: List[tr.Event] = []
    for e in launches:
        if out and e.start_ns < out[-1].end_ns:
            continue         # called from inside another launch
        out.append(e)
    return out


def attribute(planes: Sequence[tr.Plane],
              named: Optional[Dict[str, Dict[str, str]]] = None) -> Optional[Attribution]:
    """Attribution of the window; None without a window, a device, or the
    operator call's span.  ``named``: per device plane, event name -> op_name,
    for a trace that keeps op_names in the events' metadata (:func:`op_names`)."""
    found = tr.window_span(planes)
    devices = [p for p in planes if tr._DEVICE_PLANE.match(p.name)]
    if found is None or not devices:
        return None
    host_line, win = found
    lo, hi = win.start_ns, win.end_ns
    host_spans = _Spans([e for e in host_line.events if e.end_ns > lo and e.start_ns < hi])
    per_dev = []
    for p in devices:
        ops = [e for ln in p.lines if ln.name == tr.OPS_LINE for e in ln.events
               if e.end_ns > lo and e.start_ns < hi]
        modules = sorted((e for ln in p.lines if ln.name == MODULES_LINE for e in ln.events
                          if e.end_ns > lo and e.start_ns < hi), key=lambda e: e.start_ns)
        paths = [scope_of(e, (named or {}).get(p.name)) for e in ops]
        per_dev.append((p, ops, modules, paths))
    if not any(s.name == ROOT_SPAN for s in host_spans.spans) and not any(
            ROOT_SPAN in path for _, _, _, paths in per_dev for path in paths):
        return None
    total, own, calls = host_times(host_spans.spans, lo, hi)
    cuts = sorted({x for e in host_spans.spans for x in (e.start_ns, e.end_ns)})
    flows = _Flows(planes, host_line)
    launches = None
    hows = set()
    n = len(devices)
    device_s: Dict[str, float] = {}
    kernel_s: Dict[str, float] = {}
    busy = 0.0
    idle: Dict[str, float] = {}
    for p, ops, modules, paths in per_dev:
        starts = [m.start_ns for m in modules]
        module_of = [_covering(modules, starts, e.start_ns) for e in ops]
        launched: Dict[int, Optional[str]] = {}
        for m in modules:
            t = flows.launch_time(m)
            launched[id(m)] = None if t is None else host_spans.at(t)
        way = "launch"
        if modules and not any(v is not None for v in launched.values()):
            if launches is None:
                launches = _outermost_launches(host_line, lo, hi)
            if len(launches) != len(modules):
                raise AttributionFailed(
                    f"{p.name}: {len(launches)} launches in the window and "
                    f"{len(modules)} module executions; they cannot be matched in order")
            launched = {id(m): host_spans.at(e.start_ns) for m, e in zip(modules, launches)}
            way = "order"
        # the outermost program scope its scoped operations share, per module
        roots: Dict[str, List[str]] = {}
        for m, path in zip(module_of, paths):
            if m is not None and path:
                roots[m.name] = _shared(roots[m.name], path) if m.name in roots else path

        def span_of(i: int) -> str:
            if paths[i]:
                hows.add("scope")
                return paths[i][-1]
            m = module_of[i]
            if m is None:
                return NO_SPAN
            if launched.get(id(m)):
                hows.add(way)
                return launched[id(m)]
            if roots.get(m.name):
                hows.add("module")
                return roots[m.name][0]
            return NO_SPAN

        for i, sec in leaf_segments(ops, lo, hi).items():
            name = span_of(i)
            device_s[name] = device_s.get(name, 0.0) + sec / n
            if tr.kernel_of(ops[i]):
                kernel_s[name] = kernel_s.get(name, 0.0) + sec / n
            busy += sec / n
        merged = tr.merge(tr.clip([(e.start_ns, e.end_ns) for e in ops], lo, hi))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, t in zip(edges[0::2], edges[1::2]):
            inner = cuts[bisect.bisect_right(cuts, s):bisect.bisect_left(cuts, t)]
            pts = [s] + inner + [t]
            for a, b in zip(pts, pts[1:]):
                if b > a:
                    name = host_spans.at((a + b) / 2) or tr.WINDOW_SPAN
                    idle[name] = idle.get(name, 0.0) + (b - a) * 1e-9 / n
    lost = device_s.get(NO_SPAN, 0.0)
    if busy > 0 and lost > MAX_UNATTRIBUTED * busy:
        raise AttributionFailed(
            f"{lost:.6f} s of {busy:.6f} s busy in the window found no program span "
            f"(more than {MAX_UNATTRIBUTED:.0%})")
    return Attribution(how="+".join(sorted(hows)) or "none", host_s=total, host_self_s=own,
                       host_calls=calls, device_s=device_s, kernel_s=kernel_s, idle_s=idle,
                       busy_s=busy)


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) of a protobuf message in ``buf[i:end]``; a
    length-delimited value is its (start, end), fixed-width ones are skipped."""
    while i < end:
        key, i = _varint(buf, i)
        kind, value = key & 7, None
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = (i, i + size), i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {kind} in an XSpace")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_names(path: str, stat: str = SCOPE_STAT) -> Dict[str, Dict[str, str]]:
    """Per device plane of an ``.xplane.pb``, event name -> the ``stat`` of its
    event metadata, where a TPU trace keeps an operation's op_name.

    ``jax.profiler.ProfileData`` gives an event's own stats, not its
    metadata's, so this reads the file's protobuf fields directly (tsl
    ``xplane.proto``: XSpace.planes = 1; XPlane.name = 2, event_metadata = 4,
    stat_metadata = 5; XEventMetadata.name = 2, stats = 5; XStatMetadata
    .name = 2; XStat.metadata_id = 1, str_value = 5, ref_value = 7).  A name
    that two metadata entries give different values is left out."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, events, stat_names = None, [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f == 4:
                events.append(v)
            elif f == 5:
                entry = dict(_fields(buf, *v))
                if 2 in entry:
                    md = dict(_fields(buf, *entry[2]))
                    stat_names[md.get(1, 0)] = _text(buf, md[2]) if 2 in md else ""
        if name is None or not tr._DEVICE_PLANE.match(name):
            continue
        wanted = {k for k, v in stat_names.items() if v == stat}
        got: Dict[str, Optional[str]] = {}
        for v in events:
            entry = dict(_fields(buf, *v))
            if 2 not in entry:
                continue
            ev_name = value = None
            for g, x in _fields(buf, *entry[2]):
                if g == 2:
                    ev_name = _text(buf, x)
                elif g == 5:
                    st = dict(_fields(buf, *x))
                    if st.get(1) in wanted:
                        value = _text(buf, st[5]) if 5 in st else stat_names.get(st.get(7))
            if ev_name is not None and value is not None:
                got[ev_name] = value if got.get(ev_name, value) == value else None
        out[name] = {k: v for k, v in got.items() if v is not None}
    return out


def of(run, trace_dir: Optional[str] = None) -> Optional[Attribution]:
    """The attribution of ``run``'s traced window, or None where the run was
    not traced on a device or its program has no spans.  Computed once per
    run, kept on it as ``run.attribution``, and logged as one
    ``{"attribution": ...}`` line."""
    if not hasattr(run, "attribution"):
        run.attribution = None
        if trace_dir is None:
            from bench import harness

            trace_dir = harness.TRACE_DIR
        path = tr.find_xplane(trace_dir) if run.trace is not None else None
        if path is not None:
            run.attribution = attribute(tr.load(path), op_names(path))
        if run.attribution is not None:
            print(json.dumps({"attribution": run.attribution.summary()}), flush=True)
    return run.attribution
