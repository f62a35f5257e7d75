"""Reduction of a profiler trace to device busy time, per-op and per-kernel
device time, and idle gaps attributed to the host spans around them.

Works on plain event lists so it can be checked on a small synthetic trace:
``reduce(device_ops, host_spans, window)`` with device ops as
``(name, start_ns, end_ns)`` and host spans as ``(name, start_ns, end_ns)``.
``load(path)`` reads those lists from an ``.xplane.pb`` file.

The spans that name what the host was doing are the harness's own
(``HOST_SPANS``) and the program's (``router.*``, ``pool.*``,
``engine.*``). Spans of one thread nest, so at each instant one span is
the innermost (``nest``, ``innermost``); an idle gap is named after the
innermost span at its midpoint. Names are compared with any ``#...``
metadata suffix removed; host events that neither the program nor the
harness names (other threads, the XLA client, the ``window`` span) are
left out.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)
Nested = Tuple[str, float, float, Optional[int]]   # name, start, end, parent

# the harness's own spans, and the prefixes of the program's
HOST_SPANS = ("router", "submit", "wait", "tier0.step", "tier1.step",
              "tier2.step", "tier3.step")
PROGRAM_PREFIXES = ("router.", "pool.", "engine.")
WINDOW_SPAN = "window"
IDLE = "host:idle"


def merge(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def idle_gaps(busy: List[Tuple[float, float]], window: Tuple[float, float]
              ) -> List[Tuple[float, float]]:
    """The window's intervals outside ``busy`` (sorted, disjoint, inside
    the window): those in which no device op runs."""
    lo, hi = window
    gaps, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    return gaps


def base(name: str) -> str:
    """A span's name without the metadata the profiler may append."""
    return name.split("#", 1)[0]


def is_span(name: str) -> bool:
    return name in HOST_SPANS or name.startswith(PROGRAM_PREFIXES)


def nest(host: Sequence[Event]) -> List[Nested]:
    """The program's and the harness's spans in start order, each with the
    index of the span it lies in (None at the top). A span that outlasts
    its parent is cut at the parent's end."""
    evs = sorted(((base(n), s, e) for n, s, e in host if is_span(base(n))),
                 key=lambda x: (x[1], -x[2]))
    out: List[Nested] = []
    stack: List[int] = []
    for n, s, e in evs:
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        parent = stack[-1] if stack else None
        if parent is not None:
            e = min(e, out[parent][2])
        out.append((n, s, e, parent))
        stack.append(len(out) - 1)
    return out


def innermost(spans: List[Nested]) -> List[Tuple[float, float, int]]:
    """Time cut into disjoint (start, end, span index) pieces, each piece
    covered by that span and by none nested deeper; time no span covers
    is left out."""
    segs: List[Tuple[float, float, int]] = []
    stack: List[int] = []
    t = 0.0
    for i, (_, s, _, _) in enumerate(spans):
        while stack and spans[stack[-1]][2] <= s:
            j = stack.pop()
            if spans[j][2] > t:
                segs.append((t, spans[j][2], j))
            t = max(t, spans[j][2])
        if stack and s > t:
            segs.append((t, s, stack[-1]))
        stack.append(i)
        t = s
    while stack:
        j = stack.pop()
        if spans[j][2] > t:
            segs.append((t, spans[j][2], j))
        t = max(t, spans[j][2])
    return segs


def name_gaps(gaps: List[Tuple[float, float]], spans: List[Nested],
              segs: List[Tuple[float, float, int]], top: int
              ) -> List[Tuple[str, float]]:
    """The ``top`` longest gaps, longest first, as (``during <span>``,
    seconds), each named after the innermost of ``spans`` (``segs``: their
    ``innermost`` pieces) at its midpoint, ``host:idle`` where none is."""
    starts = [s for s, _, _ in segs]

    def name_at(t: float) -> str:
        k = bisect.bisect_right(starts, t) - 1
        return spans[segs[k][2]][0] if k >= 0 and t < segs[k][1] else IDLE

    return [(f"during {name_at((s + e) / 2)}", (e - s) / 1e9)
            for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]]


def reduce(device_ops: Sequence[Event], host_spans: Sequence[Event],
           window: Tuple[float, float], top: int = 10) -> dict:
    """Busy seconds (union of device-op intervals inside the window), the
    device seconds of every op name, and the ``top`` longest idle gaps,
    each named after the innermost harness or program span that covers
    its midpoint (``host:idle`` when none does)."""
    lo, hi = window
    ops = clip(device_ops, lo, hi)
    busy = merge((s, e) for _, s, e in ops)
    busy_ns = sum(e - s for s, e in busy)
    by_name: Dict[str, float] = {}
    for n, s, e in ops:
        by_name[n] = by_name.get(n, 0.0) + (e - s)
    spans = nest(host_spans)
    named = name_gaps(idle_gaps(busy, window), spans, innermost(spans), top)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "op_s": {n: v / 1e9 for n, v in by_name.items()},
        "device_ops": sorted(((short(n), v / 1e9)
                              for n, v in by_name.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": named,
    }


def short(name: str, width: int = 160) -> str:
    """An op's name as the breakdown shows it: its HLO text, cut short."""
    return name if len(name) <= width else name[:width - 3] + "..."


def kernel_seconds(op_s: Dict[str, float], pattern: str,
                   labels: Dict[str, str] = None) -> float:
    """Device seconds of the ops whose label (the op's text in the trace
    with the profiler's stats, see ``load``; the name where there is no
    label) matches the regular expression ``pattern``."""
    labels = labels or {}
    rx = re.compile(pattern)
    return sum(v for n, v in op_s.items() if rx.search(labels.get(n, n)))


def load(path: str) -> Tuple[List[Event], List[Event], dict]:
    """Device ops of the first TPU plane's op line, every host event, and
    a summary of the planes and lines found, with ``labels``: each device
    op name with the profiler's stats of its first event (the op's
    metadata, where a kernel's name can be found)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device: List[Event] = []
    host: List[Event] = []
    seen: dict = {"labels": {}}
    dev_plane = None
    for plane in pd.planes:
        lines = list(plane.lines)
        seen[plane.name] = [ln.name for ln in lines]
        if plane.name.startswith("/device:TPU:") and dev_plane is None \
                and any(ln.name == "XLA Ops" for ln in lines):
            dev_plane = plane.name
            for ln in lines:
                if ln.name != "XLA Ops":
                    continue
                for ev in ln.events:
                    device.append((ev.name, ev.start_ns, ev.end_ns))
                    if ev.name not in seen["labels"]:
                        seen["labels"][ev.name] = " ".join(
                            [ev.name] + [f"{k}={v}" for k, v in ev.stats])
        elif plane.name.startswith("/host:"):
            for ln in lines:
                host.extend((ev.name, ev.start_ns, ev.end_ns)
                            for ev in ln.events)
    seen["device_plane"] = dev_plane
    return device, host, seen


def window_of(host: Sequence[Event]) -> Tuple[float, float]:
    """The measured window as the harness's ``window`` span marks it."""
    w = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not w:
        raise ValueError("the trace holds no 'window' span")
    return w[0]
