"""Reduction of a profiler trace to device busy time, per-op and per-kernel
device time, and idle gaps attributed to the host spans around them.

Works on plain event lists so it can be checked on a small synthetic trace:
``reduce(device_ops, host_spans, window)`` with device ops as
``(name, start_ns, end_ns)`` and host spans as ``(name, start_ns, end_ns)``.
``load(path)`` reads those lists from an ``.xplane.pb`` file.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

# the harness's own spans; an idle gap is named after the one it falls in
HOST_SPANS = ("router", "submit", "wait", "tier0.step", "tier1.step",
              "tier2.step", "tier3.step")
WINDOW_SPAN = "window"


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


def reduce(device_ops: Sequence[Event], host_spans: Sequence[Event],
           window: Tuple[float, float], top: int = 10) -> dict:
    """Busy seconds (union of device-op intervals inside the window), the
    device seconds of every op name, and the ``top`` longest idle gaps,
    each named after the innermost harness span that covers its midpoint
    (``host:idle`` when none does)."""
    lo, hi = window
    ops = clip(device_ops, lo, hi)
    busy = merge((s, e) for _, s, e in ops)
    busy_ns = sum(e - s for s, e in busy)
    by_name: Dict[str, float] = {}
    for n, s, e in ops:
        by_name[n] = by_name.get(n, 0.0) + (e - s)
    gaps, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    spans = [h for h in host_spans if h[0] in HOST_SPANS]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        covering = [h for h in spans if h[1] <= mid <= h[2]]
        name = min(covering, key=lambda h: h[2] - h[1])[0] if covering \
            else "host:idle"
        named.append((f"during {name}", (e - s) / 1e9))
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
