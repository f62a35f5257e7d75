"""The program's phase spans in a profiler trace: where each step's host
time goes, and which phase each idle gap of the device falls in.

The serving path opens named spans (``router.*``, ``pool.*``, ``engine.*``)
at its phase boundaries, inside the harness's own spans (``router``,
``submit``, ``wait``, ``tierN.step``); ``tracing`` nests them.
``reduce(device_ops, host_events, window)`` takes the lists
``tracing.load`` reads and returns:

* ``spans``: for each (enclosing ``tierN.step`` or None, span name), over
  the spans that start in the window: ``count``, ``total_s``, and
  ``sync_s``, the seconds its ``*.sync`` descendants cover;
* ``idle_by_span``: the window's idle seconds by innermost covering span,
  ``host:idle`` where none covers;
* ``idle_gaps``: the ``top`` longest idle gaps, each named after the
  innermost span at its midpoint;
* ``step_idle_s``: idle seconds inside some ``engine.step`` span;
* ``window_s``.

``summary`` turns that into the plain JSON the harness keeps as the trace
record's ``phases``, which the ``step_host_ms.*`` and ``step_idle_share.*``
metrics read.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from .tracing import (IDLE, Event, clip, idle_gaps, innermost, merge,
                      name_gaps, nest)

TIER_STEP = re.compile(r"tier(\d+)\.step")
ENGINE_STEP = "engine.step"


def reduce(device_ops: Sequence[Event], host: Sequence[Event],
           window: Tuple[float, float], top: int = 10) -> dict:
    lo, hi = window
    spans = nest(host)
    # the enclosing tier step of each span, and whether it lies in (or is)
    # an engine step; parents precede their children
    tier: List[Optional[str]] = []
    in_step: List[bool] = []
    for n, _, _, p in spans:
        tier.append(None if p is None else (
            spans[p][0] if TIER_STEP.fullmatch(spans[p][0]) else tier[p]))
        in_step.append(n == ENGINE_STEP or (p is not None and in_step[p]))
    sync = [0.0] * len(spans)
    for i, (n, s, e, p) in enumerate(spans):
        if not n.endswith(".sync"):
            continue
        chain = []
        while p is not None and not spans[p][0].endswith(".sync"):
            chain.append(p)
            p = spans[p][3]
        if p is None:          # outermost sync: count it in every ancestor
            for a in chain:
                sync[a] += e - s
    table: Dict[Tuple[Optional[str], str], dict] = {}
    for i, (n, s, e, _) in enumerate(spans):
        if not lo <= s < hi:
            continue
        row = table.setdefault((tier[i], n),
                               {"count": 0, "total_s": 0.0, "sync_s": 0.0})
        row["count"] += 1
        row["total_s"] += (e - s) / 1e9
        row["sync_s"] += sync[i] / 1e9

    gaps = idle_gaps(merge((s, e) for _, s, e in clip(device_ops, lo, hi)),
                     window)
    segs = innermost(spans)
    by_span: Dict[str, float] = {}
    step_idle = 0.0
    j = 0
    for g0, g1 in gaps:
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        covered = 0.0
        k = j
        while k < len(segs) and segs[k][0] < g1:
            o = min(g1, segs[k][1]) - max(g0, segs[k][0])
            if o > 0:
                i = segs[k][2]
                by_span[spans[i][0]] = by_span.get(spans[i][0], 0.0) + o
                covered += o
                if in_step[i]:
                    step_idle += o
            k += 1
        by_span[IDLE] = by_span.get(IDLE, 0.0) + (g1 - g0) - covered

    return {"window_s": (hi - lo) / 1e9, "spans": table,
            "idle_by_span": {n: v / 1e9 for n, v in by_span.items()},
            "idle_gaps": name_gaps(gaps, spans, segs, top),
            "step_idle_s": step_idle / 1e9}


def step_host_ms(ph: dict, tier: int) -> Optional[float]:
    """Mean host time of tier ``tier``'s engine steps that start in the
    window, less the part their ``*.sync`` children spend: the Python the
    step runs between and around its launches."""
    row = ph["spans"].get((f"tier{tier}.step", ENGINE_STEP))
    if not row or not row["count"]:
        return None
    return 1e3 * (row["total_s"] - row["sync_s"]) / row["count"]


def step_idle_share(ph: dict) -> Optional[float]:
    """Share of the window in which no device op runs while the host is
    inside some engine step (%)."""
    if ph["window_s"] <= 0:
        return None
    return 100.0 * ph["step_idle_s"] / ph["window_s"]


def summary(ph: dict) -> dict:
    """The readings and both tables, as plain JSON."""
    tiers = sorted({int(TIER_STEP.fullmatch(t).group(1))
                    for t, _ in ph["spans"] if t is not None})
    return {
        "step_host_ms": {f"tier{t}": step_host_ms(ph, t) for t in tiers},
        "step_idle_share": step_idle_share(ph),
        "idle_by_span": dict(sorted(ph["idle_by_span"].items(),
                                    key=lambda x: -x[1])),
        "spans": [{"tier": t, "span": n, **row} for (t, n), row in
                  sorted(ph["spans"].items(),
                         key=lambda x: (x[0][0] or "", x[0][1]))],
    }
