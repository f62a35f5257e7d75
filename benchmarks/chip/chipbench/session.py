"""Helpers of the scripts that keep one built pool for many phases (the
knee sweep, the readings that set the check limits): one process, one
compile, new traffic or new weights per phase."""
from __future__ import annotations

import json
import time
from pathlib import Path
from typing import List

import numpy as np

from . import harness, spec, traffic

OUT_DIR = spec.ROOT / "chiprun_out"


def phase_mix(mix: dict, warm_s: float) -> dict:
    """The mix with its own warm-up and no arrivals after the window."""
    return {**mix, "warmup_s": warm_s, "drain_s": 0.0}


def backlog(run: harness.Run) -> int:
    """Requests queued for a slot, over all tiers."""
    return sum(len(t.engine.sched.pending) for t in run.tiers)


def serve_phase(run: harness.Run, mix: dict, rate: float, seconds: float,
                warm_s: float, drain_cap_s: float) -> dict:
    """Serve warm-up and a window at ``rate``, then stop the arrivals and
    let every submitted request finish (at most ``drain_cap_s``)."""
    m = phase_mix(mix, warm_s)
    arrivals = traffic.schedule(m, rate, seconds)
    origin = time.monotonic()
    loop = harness.OpenLoop(run, arrivals, origin)
    w0 = origin + warm_s
    w1 = w0 + seconds
    loop.serve_until(w0)
    backlog0 = backlog(run)
    loop.serve_until(w1)
    backlog1 = backlog(run)
    loop.serve_until(w1 + drain_cap_s, done=lambda: not run.pool.has_work)
    return {"loop": loop, "window": (w0, w1), "backlog": (backlog0, backlog1),
            "drained": not run.pool.has_work}


def prompt_lengths(mix: dict, rates: List[float], seconds: float,
                   warm_s: float) -> List[int]:
    """Every prompt length the phases at these rates will send (the
    lengths are the same for every seed)."""
    m = phase_mix(mix, warm_s)
    return sorted({len(a.tokens) for r in rates
                   for a in traffic.schedule(m, r, seconds)})


def write_out(name: str, obj) -> Path:
    OUT_DIR.mkdir(exist_ok=True)
    path = OUT_DIR / name
    path.write_text(json.dumps(obj, indent=1))
    return path


def lateness_ms(loop: harness.OpenLoop) -> dict:
    late = [1e3 * (s.submit - s.due) for s in loop.items if s.req is not None]
    return {"mean": float(np.mean(late)) if late else None,
            "max": float(np.max(late)) if late else None}
