"""Reduce a profiler trace (``.xplane.pb``) to the device figures the metrics read.

The trace is read with nothing but JAX (``jax.profiler.ProfileData``) into
plain :class:`Plane` / :class:`Line` / :class:`Event` records, so the
reduction below can be checked on a synthetic trace.

* Device operations are the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane.  Busy time is the union of their intervals
  inside the window; the idle share is 1 - busy / window.
* The window is the host span ``bench.window`` that the benchmark opens
  around the measured loop.
* A kernel is an operation whose name, or one of whose string stats, holds a
  kernel name of :data:`KERNELS` as a whole word (so ``spmv_csrk`` matches
  the Pallas call, not the ``spmv_csrk_tiles_pallas`` wrapper around it), or
  the Mosaic custom-call target ``tpu_custom_call``.
  Kernel time is the union of kernel intervals; glue is busy less kernel.
  A device that is busy in the window with no kernel among its operations
  is an error (:class:`NoKernelFound`), so that a kernel the names above
  miss fails the run instead of leaving the kernel metrics out.
* Each idle gap is cut at the edges of the host spans on the thread that
  opened the window, and each piece is named by the innermost span that
  covers it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

#: The Pallas kernels' names (``pallas_call(name=...)`` in ``repro.kernels``).
KERNELS = ("spmv_csrk", "spmv_sellcs", "spmv_segsum", "spmv_dia")

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_HOST_PLANE = "/host:CPU"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: Tuple[Tuple[str, object], ...] = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(frozen=True)
class Line:
    name: str
    events: Tuple[Event, ...]


@dataclasses.dataclass(frozen=True)
class Plane:
    name: str
    lines: Tuple[Line, ...]


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    return paths[-1] if paths else None


def load(path: str) -> List[Plane]:
    """Read an ``.xplane.pb`` into plain records."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for p in data.planes:
        lines = []
        for ln in p.lines:
            evs = tuple(Event(e.name, float(e.start_ns), float(e.duration_ns),
                              tuple((str(k), v) for k, v in e.stats))
                        for e in ln.events)
            lines.append(Line(ln.name, evs))
        planes.append(Plane(p.name, tuple(lines)))
    return planes


def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals as sorted, disjoint intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


_WORD = {k: re.compile(rf"(?<![A-Za-z0-9_]){re.escape(k)}(?![A-Za-z0-9_])") for k in KERNELS}


def kernel_of(event: Event) -> Optional[str]:
    """The kernel an operation runs, or None for every other operation.

    A Mosaic custom call (``tpu_custom_call``) that names none of
    :data:`KERNELS` is still a Pallas kernel: every one in this library is.
    """
    texts = [event.name] + [v for _, v in event.stats if isinstance(v, str)]
    for k, pat in _WORD.items():
        if any(pat.search(t) for t in texts):
            return k
    return "pallas" if any("tpu_custom_call" in t for t in texts) else None


def window_span(planes: Sequence[Plane]) -> Optional[Tuple[Line, Event]]:
    """The host thread and the span that mark the measured window."""
    for p in planes:
        if p.name != _HOST_PLANE:
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name == WINDOW_SPAN:
                    return ln, e
    return None


class NoKernelFound(RuntimeError):
    """The device was busy in the window, and no operation was a kernel."""


@dataclasses.dataclass
class TraceSummary:
    """Device figures of the window, averaged over the chips traced."""

    chips: int
    window_s: float
    busy_s: float
    kernel_s: float
    op_s: Dict[str, float]          # device seconds per operation name
    gaps: List[Tuple[str, float]]   # (host span, seconds) of every idle gap

    @property
    def glue_s(self) -> float:
        return self.busy_s - self.kernel_s

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10, width: int = 160) -> List[List]:
        """The ``n`` busiest operations; a TPU names each by its whole HLO
        instruction, which is cut to its first ``width`` characters."""
        return [[k[:width], v] for k, v in sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[List]:
        by = {}
        for name, s in self.gaps:
            by[name] = by.get(name, 0.0) + s
        return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def _op_name(e: Event) -> str:
    module = dict(e.stats).get("hlo_module")
    return f"{module}/{e.name}" if isinstance(module, str) and module else e.name


def reduce(planes: Sequence[Plane]) -> Optional[TraceSummary]:
    """Device busy, kernel and idle time inside the window; None without a device."""
    found = window_span(planes)
    devices = [p for p in planes if _DEVICE_PLANE.match(p.name)]
    if found is None or not devices:
        return None
    host_line, win = found
    lo, hi = win.start_ns, win.end_ns
    busy = kern = 0.0
    op_s: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    host = _HostSpans([e for e in host_line.events
                       if e is not win and e.end_ns > lo and e.start_ns < hi])
    for p in devices:
        ops = [e for ln in p.lines if ln.name == OPS_LINE for e in ln.events]
        ops = [e for e in ops if e.end_ns > lo and e.start_ns < hi]
        merged = merge(clip([(e.start_ns, e.end_ns) for e in ops], lo, hi))
        busy += total(merged)
        kern += total(merge(clip([(e.start_ns, e.end_ns) for e in ops if kernel_of(e)], lo, hi)))
        for e in ops:
            (s, t), = clip([(e.start_ns, e.end_ns)], lo, hi)
            op_s[_op_name(e)] = op_s.get(_op_name(e), 0.0) + (t - s) * 1e-9 / len(devices)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, t in zip(edges[0::2], edges[1::2]):
            if t > s:
                gaps += [(name, d * 1e-9 / len(devices)) for name, d in host.split(s, t)]
    n = len(devices)
    if busy > 0 and kern == 0:
        top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
        raise NoKernelFound(f"{busy * 1e-9 / n:.6f} s busy in the window and no kernel among "
                            f"the device operations; the busiest: {top}")
    return TraceSummary(chips=n, window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n,
                        kernel_s=kern * 1e-9 / n, op_s=op_s, gaps=gaps)


class _HostSpans:
    """Innermost host span at a time: the latest-starting span that covers it."""

    def __init__(self, spans: Sequence[Event]):
        self.spans = sorted(spans, key=lambda e: e.start_ns)
        self.starts = [e.start_ns for e in self.spans]
        self.ends = sorted(e.end_ns for e in self.spans)

    def split(self, s: float, t: float) -> List[Tuple[str, float]]:
        """Pieces of [s, t) between host span edges, each with its span's name."""
        cuts = set(self.starts[bisect.bisect_right(self.starts, s):bisect.bisect_left(self.starts, t)])
        cuts |= set(self.ends[bisect.bisect_right(self.ends, s):bisect.bisect_left(self.ends, t)])
        edges = [s] + sorted(cuts) + [t]
        return [(self.at((a + b) / 2), b - a) for a, b in zip(edges, edges[1:])]

    def at(self, t: float, reach: int = 512) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        for e in self.spans[max(i - reach, -1) + 1:i + 1][::-1]:
            if t < e.end_ns:
                return e.name
        return WINDOW_SPAN
