"""One run of a cell, from the built pool to the result line."""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Optional

import jax
import numpy as np

from . import harness, phases, spec, tracing, traffic


def compile_counter() -> dict:
    """XLA backend compiles and their seconds, and persistent-cache hits,
    counted from JAX's own monitoring events."""
    from jax._src import dispatch
    tally = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            tally["compiles"] += 1
            tally["compile_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            tally["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return tally


def log(msg: str) -> None:
    print(msg, flush=True)


def rate_of(config: dict, mix: dict) -> float:
    knee = config.get("knee_rps")
    if not knee:
        raise ValueError(f"{config['name']}: no knee_rps in its file; run "
                         "benchmarks/chip/sweep.py and record it there")
    return float(knee) * float(mix["load"])


def window_done(loop: harness.OpenLoop, lo: float, hi: float) -> Callable:
    """True once every request due in [lo, hi) has its first token or has
    been retired."""
    def done():
        for s in loop.items:
            if lo <= s.due < hi and (s.req is None or not (
                    s.req.token_t or s.req.done)):
                return False
        return True
    return done


def serve_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
               t_start: float, tally: dict, rate: Optional[float] = None,
               fault: Optional[Callable] = None,
               run: Optional[harness.Run] = None) -> dict:
    """Serve one run of ``cell`` and return its record (what the metric
    readers read) with the run's pool and loop, before any check.
    ``run``: a pool already built for this cell, given new weights from
    ``seed`` (tests reuse one build); ``fault``: called on the built run
    before warm-up, to break the timed path on purpose (tests)."""
    config, mix = cell.config, cell.mix
    rate = rate_of(config, mix) if rate is None else rate
    arrivals = traffic.schedule(mix, rate, seconds)
    cal = traffic.calibration_prompts(mix, int(config["router"][
        "calibration_prompts"]))
    if run is None:
        run = harness.Run(config, seed)
    else:
        run.reseed(seed)
    if fault is not None:
        fault(run)
    t = time.monotonic()
    prompt_lens = [len(a.tokens) for a in arrivals]
    router_lens = {traffic.bucket(L, 32) for L in
                   prompt_lens + [len(p) for p in cal]}
    n_shapes = run.warm(prompt_lens, router_lens)
    log(f"warm: {n_shapes} step shapes in {time.monotonic() - t:.1f} s "
        f"(compiles so far {tally['compiles']}, {tally['compile_s']:.1f} s; "
        f"cache hits {tally['cache_hits']})")
    thr = run.calibrate(cal, float(config["router"]["tier0_share"]))
    log(f"router gate {thr:.6f} from {len(cal)} calibration prompts; "
        f"rate {rate:.4f} req/s")
    origin = time.monotonic()
    loop = harness.OpenLoop(run, arrivals, origin)
    w0 = origin + float(mix["warmup_s"])
    w1 = w0 + seconds
    loop.serve_until(w0)
    setup_s = time.monotonic() - t_start
    stats0, c0 = run.stats(), dict(tally)
    trace_dir = None
    window_span = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window_span = jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN)
        window_span.__enter__()
        run.record_work(True)
    loop.serve_until(w1)
    if trace:
        run.record_work(False)
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    stats1, c1 = run.stats(), dict(tally)
    drain = float(mix["drain_s"])
    if drain > 0:
        loop.serve_until(w1 + drain, done=window_done(loop, w0, w1))
    drain_end = time.monotonic()
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    rec = {
        "cell": cell.name, "seed": seed, "seconds": seconds, "rate": rate,
        "window": [w0, w1], "drain_end": drain_end, "setup_s": setup_s,
        "requests": [harness.request_record(s) for s in loop.items
                     if s.req is not None],
        "due_not_submitted": sum(1 for s in loop.items
                                 if s.req is None and w0 <= s.due < w1),
        "spans": run.spans, "stats": [stats0, stats1],
        "work": run.work if trace else None,
        "tiers": [{"name": t.name, "kind": t.spec["reference"],
                   "model": t.spec["model"]} for t in run.tiers],
        "router": config["router"],
        "peaks": spec.load_peaks(dev.device_kind) if dev.platform == "tpu"
        else None,
        "compiles_in_window": c1["compiles"] - c0["compiles"],
        "compile_s_in_window": c1["compile_s"] - c0["compile_s"],
        "memory_peak_bytes": mem, "threshold": thr, "trace": None,
    }
    log_run(rec, loop, run)
    if trace_dir is not None:
        try:
            rec["trace"] = reduce_trace(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return {"rec": rec, "run": run, "loop": loop}


def reduce_trace(trace_dir: str) -> dict:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no xplane file under "
                           f"{trace_dir}")
    device, host, seen = tracing.load(files[0])
    labels = seen.pop("labels")
    log(f"trace: {len(device)} device ops, {len(host)} host events; "
        f"device plane {seen.get('device_plane')}; lines "
        f"{ {k: v[:6] for k, v in seen.items() if k != 'device_plane'} }")
    return reduce_events(device, host, labels)


def reduce_events(device: list, host: list, labels: dict) -> dict:
    """The trace record: ``tracing.reduce`` of the window, each op's label,
    and under ``phases`` the program's spans reduced (``phases.summary``)."""
    window = tracing.window_of(host)
    out = tracing.reduce(device, host, window)
    out["labels"] = {n: labels.get(n, n) for n in out["op_s"]}
    out["phases"] = phases.summary(phases.reduce(device, host, window))
    log(f"trace: busy {out['busy_s']:.4f} s of {out['window_s']:.4f} s")
    for n, v in sorted(out["op_s"].items(), key=lambda x: -x[1])[:10]:
        log(f"trace op {v:.4f} s: {out['labels'][n][:400]}")
    for n, v in out["phases"]["idle_by_span"].items():
        log(f"idle {v:.4f} s under {n}")
    for row in out["phases"]["spans"]:
        log(f"span {row['tier']} {row['span']}: {row['count']} x, "
            f"{row['total_s']:.4f} s, sync {row['sync_s']:.4f} s")
    return out


def log_run(rec: dict, loop: harness.OpenLoop, run: harness.Run) -> None:
    """What the result line leaves out, on earlier lines."""
    w0, w1 = rec["window"]
    for name, lo, hi in (("warm-up", -np.inf, w0), ("window", w0, w1)):
        late = [s.submit - s.due for s in loop.items
                if s.req is not None and lo <= s.due < hi]
        if late:
            log(f"generator lateness ({name}): mean "
                f"{1e3 * np.mean(late):.3f} ms, max {1e3 * np.max(late):.3f}"
                f" ms over {len(late)} submissions")
    log(f"due in the window but never submitted: "
        f"{rec['due_not_submitted']}")
    log(f"compiles inside the window: {rec['compiles_in_window']} "
        f"({rec['compile_s_in_window']:.3f} s)")
    split = np.bincount([s.tier for s in loop.items if s.req is not None],
                        minlength=len(run.tiers))
    log(f"routing split (all submitted): {split.tolist()}; gate "
        f"{rec['threshold']:.6f}")
    for i, t in enumerate(run.tiers):
        a, b = rec["stats"][0][i], rec["stats"][1][i]
        log(f"tier {i} {t.name}: window decode steps "
            f"{b['decode_steps'] - a['decode_steps']}, decode tokens "
            f"{b['decode_tokens'] - a['decode_tokens']}, prefill tokens "
            f"{b['prefill_tokens'] - a['prefill_tokens']}, dispatches "
            f"{b['prefill_dispatches'] - a['prefill_dispatches']}; calls "
            f"{run.pool.meter.summary()[t.name]['calls']} retired so far")
    if rec["memory_peak_bytes"] is not None:
        log(f"peak device memory: {rec['memory_peak_bytes']:,} bytes")


def result(cell: spec.Cell, out: dict, trace: bool, checks: dict) -> dict:
    rec = out["rec"]
    w0, w1 = rec["window"]
    due = [r for r in rec["requests"] if r["segment"] == "window"]
    failed = sum(1 for r in due if not r["token_t"]
                 or (r["done"] and r["reason"] not in harness.FINISHED))
    failed += rec["due_not_submitted"]
    metrics = spec.read_metrics(cell.per_layer if trace else cell.end_to_end,
                                rec)
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": rec["memory_peak_bytes"]}
    line = {"correct": harness.passed(checks),
            "attempted": len(due) + rec["due_not_submitted"],
            "failed": failed, "metrics": metrics, "device": device}
    if trace and rec["trace"] is not None:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        line["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                             "idle_gaps": rec["trace"]["idle_gaps"]}
    line["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                      for k, v in checks.items()}
    return line


def run_checks(cell: spec.Cell, out: dict, seed: int,
               control: Optional[str] = None) -> dict:
    run, loop = out["run"], out["loop"]
    checks = harness.audit(run, loop)
    run.free_state()
    t = time.monotonic()
    limits = cell.config["checks"]
    checks.update({k: v for k, v in harness.check(
        run, loop, seed, limits, control=control).items()
        if k in limits or v["limit"] == 0.0})
    log(f"reference check took {time.monotonic() - t:.1f} s")
    return checks


def print_checks(checks: dict) -> None:
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
