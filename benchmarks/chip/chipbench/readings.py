"""Shared arithmetic of the metric readers (``metrics/<name>.py``): each
reader picks what it needs from a run's record."""
from __future__ import annotations

from typing import List, Optional, Tuple

from . import spec, stats, work


def window(rec) -> Tuple[float, float]:
    return tuple(rec["window"])


def in_window(spans, rec) -> List[Tuple[float, float]]:
    """Spans that started inside the measured window."""
    lo, hi = window(rec)
    return [(s, e) for s, e in spans if lo <= s < hi]


def due_in_window(rec) -> List[dict]:
    return [r for r in rec["requests"] if r["segment"] == "window"]


def ttft_s(rec) -> List[float]:
    """Due time to first token of every request due in the window; one
    that never answered stands at due time to the end of the drain, above
    every answered one."""
    out = []
    for r in due_in_window(rec):
        out.append(r["token_t"][0] - r["due"] if r["token_t"]
                   else rec["drain_end"] - r["due"])
    lo, hi = window(rec)
    out += [rec["drain_end"] - hi] * rec["due_not_submitted"]
    return out


def gaps_s(rec) -> List[float]:
    """Every gap between consecutive output tokens of one request whose
    later token was emitted inside the window, both tiers."""
    lo, hi = window(rec)
    out = []
    for r in rec["requests"]:
        t = r["token_t"]
        out += [b - a for a, b in zip(t, t[1:]) if lo <= b < hi]
    return out


def tokens_in_window(rec, tier: Optional[int] = None) -> int:
    lo, hi = window(rec)
    return sum(1 for r in rec["requests"]
               if tier is None or r["tier"] == tier
               for t in r["token_t"] if lo <= t < hi)


def p95_ms(values) -> Optional[float]:
    return 1e3 * stats.percentile(values, 95) if values else None


def trace_seconds(rec, match: str) -> Optional[float]:
    from .tracing import kernel_seconds
    if rec.get("trace") is None:
        return None
    s = kernel_seconds(rec["trace"]["op_s"], match,
                       rec["trace"].get("labels"))
    return s if s > 0 else None


def phases(rec) -> Optional[dict]:
    """The traced window's program spans, reduced (``phases.summary``)."""
    tr = rec.get("trace")
    return tr.get("phases") if tr else None


def idle_share(rec) -> Optional[float]:
    tr = rec.get("trace")
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def kernel_roofline(rec, kind: str, match: str) -> Optional[float]:
    """Roofline share of a paged attention kernel over the traced window:
    the work the window's dispatches needed, over every tier whose kind
    keeps K/V in the paged cache and over those layers of it, over the
    kernel's device seconds. ``kind`` is "decode" or "prefill"."""
    secs = trace_seconds(rec, match)
    if secs is None or rec.get("work") is None or rec.get("peaks") is None:
        return None
    count = work.paged_decode_work if kind == "decode" \
        else work.paged_prefill_work
    flops = byts = 0.0
    for i, t in enumerate(rec["tiers"]):
        layers = spec.tier_kind(t["kind"]).paged_layers(t["model"])
        if not layers:
            continue
        items = rec["work"].get(f"tier{i}.{kind}", [])
        f, b = count(t["model"], [c for _, cs in items for c in cs],
                     layers=layers)
        flops, byts = flops + f, byts + b
    if flops == 0:
        return None
    return work.roofline_share(flops, byts, secs, rec["peaks"])


def model_flops(rec) -> float:
    """Model FLOPs of all work the traced window carried: router scoring,
    prefill chunks, decode tokens (each tier by its kind's
    ``token_flops``) and the LM head of every token emitted, all tiers."""
    total = 0.0
    lo, hi = window(rec)
    for r in rec["requests"]:
        if lo <= r["submit"] < hi:
            total += work.router_flops(rec["router"], r["prompt_len"])
    for i, t in enumerate(rec["tiers"]):
        m, kind = t["model"], t["kind"]
        for _, chunks in rec["work"].get(f"tier{i}.prefill", []):
            total += sum(work.prefill_flops(kind, m, s, n) for s, n in chunks)
        for _, ctx in rec["work"].get(f"tier{i}.decode", []):
            total += sum(work.token_flops(kind, m, c) for c in ctx)
        total += tokens_in_window(rec, i) * work.lm_head_flops(m)
    return total


def mfu(rec) -> Optional[float]:
    """Model FLOPs of the window's work over the summed wall time of the
    tier steps and router calls in it, times the chip's peak."""
    if rec.get("work") is None or rec.get("peaks") is None:
        return None
    busy = sum(e - s for k, spans in rec["spans"].items()
               for s, e in in_window(spans, rec))
    if busy <= 0:
        return None
    return 100.0 * model_flops(rec) / busy / rec["peaks"]["bf16_flops_per_s"]
