"""Serving throughput: dense-batch vs continuous-paged engines.

Replays one ragged request stream (ragged prompt lengths AND ragged
per-request output caps) through both serving architectures at three tiers
— small model, large model, and router-split hybrid — plus a 3-tier
cascade-routed ``ContinuousPoolEngine`` (small/medium/large, per-tier
tokens/s, TTFT, and KV high-water columns) — and reports:

  * tokens/s        — *useful* generated tokens per wall second. A token is
                      useful if it falls within the request's own output cap;
                      the dense engine has no per-request caps, so everything
                      it generates past a cap (and every decode step spent on
                      requests that already hit EOS) is counted as work but
                      not as useful output. That asymmetry is the measured
                      systems gap, not an accounting trick.
  * p50/p99 latency — per-request completion latency from stream submission.
                      Dense requests complete when their batch joins;
                      continuous requests complete when they individually
                      retire.
  * TTFT p50/p99    — submission to first emitted token. Continuous engines
                      report the real per-request first-token time (chunked
                      prefill admits long prompts without stalling decode);
                      a dense request's first token only exists when its
                      whole batch joins, so dense TTFT equals its latency.
  * inter-token p99 — worst-case gap between consecutive tokens of one
                      request (continuous only; dense emits all tokens at
                      the join). This is the column chunked prefill moves:
                      one-shot admission stalls every live decode slot for a
                      whole-prompt prefill.
  * KV high-water   — bytes of KV cache held at the worst moment: the dense
                      slab (bucket x (prompt + max_new)) vs the paged pool's
                      high-water page count.

Three serving-hot-path rows ride along: ``long_context`` serves a stream of
short live contexts on an engine provisioned for much longer prompts, with
live-bounded vs full-static page walks — decode step time must track the
live max context, not ``max_pages_per_slot``; ``heavy_admission`` floods the
engine with multi-chunk prompts — packed prefill must launch ~one kernel
per width bucket per step instead of one per PREFILLING slot;
``window_ssm`` serves the mixed stream through a 3-tier pool whose tiers
are a plain uniform-global stack, a gemma3-style sliding-window stack, and
a jamba-style SSM/hybrid stack — the two new layer kinds must stay
greedy-exact vs their dense per-layer references; ``preemption`` runs a
deterministic priority burst against a tight bounded-queue engine, so the
robustness layer's counters (preemptions, re-prefill tokens, sheds,
deadline misses) and its invariants (every request retired with a valid
finish reason, zero leaked pages, preempted outputs greedy-exact vs
uncontended runs) land in the JSON for CI to assert; ``speculative`` runs
the pool's cross-tier speculative step plane with a self-speculation draft
(same weights as the target — the deterministic high-acceptance canary)
and reports acceptance rate, target-tier steps per emitted token
(asserted < 1.0 by the CI smoke), and greedy-exactness vs the identical
non-speculative pool; ``prefix_sharing`` replays a multi-turn chat +
best-of-N fan-out stream on a shared-prefix copy-on-write engine
(serving.prefix) vs the identical stream with ``prefix_cache=0`` and
reports hit rate, prefill tokens saved (asserted > 50% by the CI smoke),
TTFT p99 on vs off, pages-shared high-water, COW splits, and the refcount
zero-leak audit; ``escalation`` serves the stream on the cheap tier of a
2-tier pool under a mid-stream quality monitor — an observe-only pass
calibrates the abort threshold at the median per-stream peak uncertainty,
then the timed pass cancels crossing streams and re-admits each one tier
up as ONE chunked prefill — and reports the escalation count (asserted
> 0 by the CI smoke), the token split across tiers, whether every
continuation is byte-identical to the upper tier decoding greedily from
(prompt + emitted prefix), and a ``per_boundary_matches_shared`` parity
flag (per-boundary cascade gates vs the legacy shared-score cascade with
identical heads). Streaming rows also
report queue-wait p50/p99 (submission to first admission). A
``padding_parity`` flag asserts the dense, continuous, and pool serve
paths agree on responses including tok.PAD tails.

Both engines are warmed up (jit compiles excluded from the timed stream):
the dense engine precompiles its buckets, and every continuous row replays
its identical request stream once un-timed before the measured pass —
packed prefill keys compiles on bucketed (batch, width, page-bound)
triples, so replaying the deterministic schedule is the reliable warmup.

Usage:
  PYTHONPATH=src python benchmarks/serving_throughput.py [--smoke]
      [--prefill-chunk W] [--prefill-pack N] [--walk-bound live|static]
      [--out BENCH_serving.json]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.routing import (CascadePolicy, HybridRouter,
                                ThresholdPolicy)
from repro.data import tokenizer as tok
from repro.launch.compile_cache import enable_compile_cache
from repro.models import (RouterConfig, build_model, init_router_encoder)
from repro.models.config import ArchConfig
from repro.serving import (ContinuousEngine, ContinuousHybridEngine,
                           ContinuousPoolEngine, Engine, HybridEngine)
from repro.serving.engine import _bucket


def tier_configs(smoke: bool):
    """(small, medium, large) — the hybrid rows use the (small, large)
    pair, the 3-tier pool row all three."""
    base = dict(family="dense", vocab_size=tok.VOCAB_SIZE,
                vocab_pad_multiple=16, head_dim=16, attn_chunk=32,
                cache_layout="paged", kv_page_size=16)
    small = ArchConfig(name="serve-small", n_layers=2, d_model=64, n_heads=4,
                       n_kv_heads=2, d_ff=128, **base)
    if smoke:
        medium = ArchConfig(name="serve-medium", n_layers=2, d_model=64,
                            n_heads=4, n_kv_heads=2, d_ff=192, **base)
        large = ArchConfig(name="serve-large", n_layers=3, d_model=64,
                           n_heads=4, n_kv_heads=2, d_ff=128, **base)
    else:
        medium = ArchConfig(name="serve-medium", n_layers=4, d_model=128,
                            n_heads=8, n_kv_heads=4, d_ff=192, **base)
        large = ArchConfig(name="serve-large", n_layers=6, d_model=128,
                           n_heads=8, n_kv_heads=4, d_ff=256, **base)
    return small, medium, large


def make_stream(rng, n: int, t_max: int):
    """Ragged prompts (padded into one (N, Lmax) array for the dense API)
    with heavy-tailed per-request output caps: most requests want a short
    answer, a few want the full budget — the regime continuous batching is
    built for. One request in eight carries a long prompt, the case where
    one-shot admission stalls every live decode slot."""
    lens = np.where(rng.random(n) < 0.125, rng.integers(32, 49, (n,)),
                    rng.integers(6, 25, (n,)))
    lmax = int(lens.max())
    toks = np.full((n, lmax), tok.PAD, np.int32)
    for i, l in enumerate(lens):
        toks[i, :l] = rng.integers(4, tok.VOCAB_SIZE, (l,))
    caps = np.where(rng.random(n) < 0.75,
                    rng.integers(2, max(3, t_max // 4), (n,)),
                    t_max).astype(np.int32)
    return toks, lens.astype(np.int32), caps


def _percentiles(lat):
    lat = np.asarray(lat)
    return {"p50_s": float(np.percentile(lat, 50)),
            "p99_s": float(np.percentile(lat, 99))}


def _streaming_metrics(reqs):
    """TTFT, queue-wait, and inter-token percentiles from per-request
    stamps. TTFT and queue percentiles skip requests that never reached a
    token / a slot (load-shed ones have neither, by design); if NO request
    qualifies, the column is NaN — the CI finiteness assertion then fails
    loudly instead of reading a fabricated 0ms as an impossibly good
    result. Same for inter-token p99 when no request emitted twice."""
    ttft = [r.ttft for r in reqs if np.isfinite(r.ttft)]
    queue = [r.queue_time for r in reqs if np.isfinite(r.queue_time)]
    gaps = [np.diff(r.token_t) for r in reqs if len(r.token_t) > 1]
    return {"ttft_p50_s": float(np.percentile(ttft, 50))
            if ttft else float("nan"),
            "ttft_p99_s": float(np.percentile(ttft, 99))
            if ttft else float("nan"),
            "queue_p50_s": float(np.percentile(queue, 50))
            if queue else float("nan"),
            "queue_p99_s": float(np.percentile(queue, 99))
            if queue else float("nan"),
            "intertoken_p99_s": float(np.percentile(np.concatenate(gaps), 99))
            if gaps else float("nan")}


def _finish_reasons(reqs):
    """Per-reason retirement counts; a nonzero context_cap means the two
    engine families served different effective workloads."""
    counts: dict = {}
    for r in reqs:
        counts[r.finish_reason] = counts.get(r.finish_reason, 0) + 1
    return counts


def _join_ttft(latencies):
    """Dense engines emit a request's tokens only at the batch join, so
    TTFT equals completion latency."""
    return {"ttft_p50_s": float(np.percentile(latencies, 50)),
            "ttft_p99_s": float(np.percentile(latencies, 99))}


def run_dense(bundle, params, stream, t_max: int, batch: int):
    toks, lens, caps = stream
    eng = Engine(bundle, params, max_new_tokens=t_max)
    eng.warmup(toks.shape[1], batch)
    useful = 0
    latencies = []
    t0 = time.monotonic()
    for i in range(0, len(toks), batch):
        r, l = eng.serve(toks[i:i + batch])
        done_t = time.monotonic() - t0
        useful += int(np.minimum(l, caps[i:i + batch]).sum())
        latencies += [done_t] * len(r)
    wall = time.monotonic() - t0
    return {
        "engine": "dense_batch",
        "requests": len(toks),
        "useful_tokens": useful,
        "generated_tokens": int(eng.stats.gen_tokens),
        "wall_s": round(wall, 4),
        "tokens_per_s": round(useful / wall, 2),
        "kv_high_water_bytes": int(eng.stats.kv_high_water_bytes),
        "padding_waste": round(eng.stats.padding_waste, 4),
        "compiles": eng.stats.compiles,
        **_percentiles(latencies),
        **_join_ttft(latencies),
    }


def _continuous(bundle, params, t_max, n_slots, prefill_chunk=None,
                prefill_pack=None, walk_bound="live"):
    # max_seq covers the longest prompt (48) + full output budget (32), so
    # no request context-caps and the dense comparison stays apples-to-apples
    return ContinuousEngine(bundle, params, max_new_tokens=t_max,
                            n_slots=n_slots, max_seq=96,
                            prefill_chunk=prefill_chunk,
                            prefill_pack=prefill_pack,
                            walk_bound=walk_bound)


def _warm_then_timed(eng, prompts, caps):
    """Run the identical stream twice through one engine: the first pass
    traces every (batch, width, page-bound) shape the deterministic greedy
    schedule will need — exhaustive shape prediction is impractical now
    that packed prefill keys compiles on pack-batch and live-bound buckets
    too — and the second pass is timed. Resets the cache high-water mark
    between passes so the KV column reflects the timed stream. Returns
    (reqs, per-pass stat deltas, wall, t0)."""
    caps = [int(c) for c in caps]
    for p_, c in zip(prompts, caps):
        eng.submit(p_, max_new_tokens=c)
    eng.run()
    eng.cache.stats.high_water_pages = eng.cache.stats.pages_in_use
    pre = dataclasses.replace(eng.stats)
    t0 = time.monotonic()
    reqs = [eng.submit(p_, max_new_tokens=c)
            for p_, c in zip(prompts, caps)]
    eng.run()
    wall = time.monotonic() - t0
    delta = {f.name: getattr(eng.stats, f.name) - getattr(pre, f.name)
             for f in dataclasses.fields(eng.stats)
             if isinstance(getattr(eng.stats, f.name), int)}
    return reqs, delta, wall, t0


def _compile_bounds(eng):
    """Recompile-guard canary: upper bounds on distinct compile keys implied
    by the engine's power-of-two bucketing. Decode keys are (bound, wstart)
    pairs (plus draft bounds when speculating); prefill keys are (batch,
    width, bound, wstart) tuples (plus verify (batch, bound) shapes). Any
    regression that un-buckets a compile-key component — raw lengths or
    live page counts reaching a jit signature — blows straight past these
    bounds, so the smoke run fails instead of silently recompiling per
    step."""
    mp = eng.cache.max_pages_per_slot
    n_bounds = len({min(_bucket(x), mp) for x in range(1, mp + 1)})
    n_wstarts = 1
    if eng.bundle.cfg.has_window_layers and eng.walk_bound == "live":
        # floor-pow2 of the first window page: {0, 1, 2, 4, ...} up to mp
        starts = {0}
        b = 1
        while b <= mp:
            starts.add(b)
            b *= 2
        n_wstarts = len(starts)
    chunk = eng.prefill_chunk
    n_widths = len({min(_bucket(x), chunk) for x in range(1, chunk + 1)})
    pack = eng.prefill_pack if eng.prefill_pack else 1  # 0 = per-slot B=1
    n_batches = len({_bucket(x) for x in range(1, pack + 1)})
    decode_bound = n_bounds * n_wstarts
    prefill_bound = n_batches * n_widths * n_bounds * n_wstarts
    if eng.draft_bundle is not None:
        decode_bound += n_bounds          # draft decode keys on bound only
        n_vbatch = len({_bucket(x) for x in range(1, eng.n_slots + 1)})
        prefill_bound += n_vbatch * n_bounds   # verify (batch, bound) keys
    return decode_bound, prefill_bound


def run_continuous(bundle, params, stream, t_max: int, n_slots: int,
                   rng, prefill_chunk=None, prefill_pack=None,
                   walk_bound="live"):
    toks, lens, caps = stream
    eng = _continuous(bundle, params, t_max, n_slots, prefill_chunk,
                      prefill_pack, walk_bound)
    prompts = [toks[i, :lens[i]] for i in range(len(toks))]
    reqs, delta, wall, t0 = _warm_then_timed(eng, prompts, caps)
    useful = sum(r.n_generated for r in reqs)
    latencies = [r.finish_t - t0 for r in reqs]
    dc_bound, pc_bound = _compile_bounds(eng)
    assert eng.stats.decode_compiles <= dc_bound, \
        (f"recompile canary: {eng.stats.decode_compiles} decode compiles "
         f"exceed the {dc_bound} distinct (bound, wstart) buckets the "
         "engine geometry allows — a compile-key component is unbucketed")
    assert eng.stats.prefill_compiles <= pc_bound, \
        (f"recompile canary: {eng.stats.prefill_compiles} prefill compiles "
         f"exceed the {pc_bound} distinct (batch, width, bound, wstart) "
         "buckets the engine geometry allows")
    return {
        "engine": "continuous_paged",
        "requests": len(toks),
        "useful_tokens": useful,
        "generated_tokens": useful,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(useful / wall, 2),
        "kv_high_water_bytes": int(eng.cache.stats.high_water_pages
                                   * eng.cache.bytes_per_page),
        # mean occupancy over ALL steps that did work — prefill-only steps
        # included, so heavy admission no longer overstates the column.
        # Step/chunk/dispatch counters are timed-pass deltas; the two
        # *_compiles counters are engine-lifetime totals (warm pass
        # included — compiles_timed is the in-window count, normally 0)
        "mean_slot_occupancy": round(
            delta["occupancy_sum"] / max(delta["steps"], 1), 2),
        "steps": delta["steps"],
        "decode_steps": delta["decode_steps"],
        "prefill_only_steps": delta["prefill_only_steps"],
        "admission_stalls": delta["admission_stalls"],
        "prefill_chunk": eng.prefill_chunk,
        "prefill_pack": eng.prefill_pack,
        "walk_bound": eng.walk_bound,
        "prefill_chunks": delta["prefill_chunks"],
        "prefill_dispatches": delta["prefill_dispatches"],
        "prefill_compiles": eng.stats.prefill_compiles,
        "decode_compiles": eng.stats.decode_compiles,
        "prefill_compile_bound": pc_bound,
        "decode_compile_bound": dc_bound,
        "compiles_timed": delta["prefill_compiles"]
        + delta["decode_compiles"],
        "prefill_stalls": delta["prefill_stalls"],
        "finish_reasons": _finish_reasons(reqs),
        **_percentiles(latencies),
        **_streaming_metrics(reqs),
    }


def _toy_router(q, mask):
    """One toy router scores every routed row — the hybrid rows' median
    split and the pool row's tercile cascade must bucket the SAME scores
    for their cost columns to be comparable."""
    rc = RouterConfig(vocab_size=tok.VOCAB_SIZE, n_layers=1, d_model=32,
                      n_heads=2, d_ff=64)
    params = init_router_encoder(jax.random.PRNGKey(0), rc)
    r = HybridRouter(params, rc, 0.5)
    scores = np.asarray(r.scores(jnp.asarray(q), jnp.asarray(mask)))
    return r, scores


def _median_router(q, mask):
    r, scores = _toy_router(q, mask)
    return r.with_threshold(float(np.median(scores)))


def run_hybrid_dense(bundles, stream, t_max, batch):
    (bs, ps_), (bl, pl_) = bundles
    toks, lens, caps = stream
    mask = (toks != tok.PAD).astype(np.float32)
    router = _median_router(toks, mask)
    small = Engine(bs, ps_, max_new_tokens=t_max)
    large = Engine(bl, pl_, max_new_tokens=t_max)
    small.warmup(toks.shape[1], batch)
    large.warmup(toks.shape[1], batch)
    for i in range(0, len(toks), batch):  # warm every batch-slice shape
        router.scores(jnp.asarray(toks[i:i + batch]),
                      jnp.asarray(mask[i:i + batch]))
    hy = HybridEngine(router, small, large)
    useful = 0
    latencies = []
    t0 = time.monotonic()
    for i in range(0, len(toks), batch):
        res = hy.serve(toks[i:i + batch], mask[i:i + batch])
        done_t = time.monotonic() - t0
        useful += int(np.minimum(res.lengths, caps[i:i + batch]).sum())
        latencies += [done_t] * len(res.lengths)
    wall = time.monotonic() - t0
    return {
        "engine": "dense_batch_hybrid",
        "requests": len(toks),
        "useful_tokens": useful,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(useful / wall, 2),
        "kv_high_water_bytes": int(small.stats.kv_high_water_bytes
                                   + large.stats.kv_high_water_bytes),
        "cost_advantage": round(hy.meter.cost_advantage, 4),
        "token_cost_advantage": round(hy.meter.token_cost_advantage, 4),
        **_percentiles(latencies),
        **_join_ttft(latencies),
    }


def run_hybrid_continuous(bundles, stream, t_max, n_slots, rng,
                          prefill_chunk=None, prefill_pack=None,
                          walk_bound="live"):
    (bs, ps_), (bl, pl_) = bundles
    toks, lens, caps = stream
    mask = (toks != tok.PAD).astype(np.float32)
    router = _median_router(toks, mask)
    small = _continuous(bs, ps_, t_max, n_slots, prefill_chunk,
                        prefill_pack, walk_bound)
    large = _continuous(bl, pl_, t_max, max(2, n_slots // 2), prefill_chunk,
                        prefill_pack, walk_bound)
    router.scores(jnp.asarray(toks), jnp.asarray(mask))
    hy = ContinuousHybridEngine(router, small, large)
    # warm pass: the identical stream traces every shape the timed pass
    # needs; the meter and high-water marks then reset so only the timed
    # stream counts
    hy.submit(toks, mask, max_new_tokens=caps)
    hy.run()
    for eng in (small, large):
        eng.cache.stats.high_water_pages = eng.cache.stats.pages_in_use
    hy.pool.meter.reset()
    t0 = time.monotonic()
    reqs, to_small, _ = hy.submit(toks, mask, max_new_tokens=caps)
    hy.run()
    wall = time.monotonic() - t0
    useful = sum(r.n_generated for r in reqs)
    latencies = [r.finish_t - t0 for r in reqs]
    bpp = small.cache.bytes_per_page
    bpl = large.cache.bytes_per_page
    return {
        "engine": "continuous_paged_hybrid",
        "requests": len(toks),
        "useful_tokens": useful,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(useful / wall, 2),
        "kv_high_water_bytes": int(
            small.cache.stats.high_water_pages * bpp
            + large.cache.stats.high_water_pages * bpl),
        "cost_advantage": round(hy.meter.cost_advantage, 4),
        "token_cost_advantage": round(hy.meter.token_cost_advantage, 4),
        "routed_small": int(to_small.sum()),
        "prefill_compiles": small.stats.prefill_compiles
        + large.stats.prefill_compiles,
        "finish_reasons": _finish_reasons(reqs),
        **_percentiles(latencies),
        **_streaming_metrics(reqs),
    }


def _tercile_cascade(q, mask):
    """3-tier cascade policy splitting the stream into rough thirds by
    router-score terciles (the ThresholdPolicy-cascade analogue of the
    hybrid rows' median split, same toy router)."""
    r, scores = _toy_router(q, mask)
    return CascadePolicy(r, (float(np.quantile(scores, 2 / 3)),
                             float(np.quantile(scores, 1 / 3))))


def _run_pool_stream(pool, names, engines, stream):
    """Warm/reset/timed replay + per-tier accounting shared by every pool
    row: warm pass over the identical stream (traces every packed shape the
    deterministic schedule needs), reset the meter and cache high-water
    marks so only the timed stream counts (see _warm_then_timed), then the
    timed pass. Returns the row skeleton: pool totals, latency columns,
    and per-tier rows (calls/tokens/tok-s/KV/TTFT) callers extend."""
    toks, lens, caps = stream
    mask = (toks != tok.PAD).astype(np.float32)
    pool.submit(toks, mask, max_new_tokens=caps)
    pool.run()
    for eng in engines:
        eng.cache.stats.high_water_pages = eng.cache.stats.pages_in_use
    pool.meter.reset()
    t0 = time.monotonic()
    reqs, tier_idx, _ = pool.submit(toks, mask, max_new_tokens=caps)
    pool.run()
    wall = time.monotonic() - t0
    useful = sum(r.n_generated for r in reqs)
    latencies = [r.finish_t - t0 for r in reqs]
    per_tier = {}
    for t, (name, eng) in enumerate(zip(names, engines)):
        treqs = [r for r, ti in zip(reqs, tier_idx) if ti == t]
        row = pool.meter.summary()[name]
        row.update({
            "tokens_per_s": round(row["gen_tokens"] / wall, 2),
            "kv_high_water_bytes": int(eng.cache.stats.high_water_pages
                                       * eng.cache.bytes_per_page),
        })
        if treqs:
            row.update({k: v for k, v in _streaming_metrics(treqs).items()
                        if k.startswith("ttft")})
        per_tier[name] = row
    return {
        "engine": "continuous_paged_pool",
        "n_tiers": len(names),
        "requests": len(toks),
        "useful_tokens": useful,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(useful / wall, 2),
        "kv_high_water_bytes": sum(t["kv_high_water_bytes"]
                                   for t in per_tier.values()),
        "cost_advantage": round(pool.meter.cost_advantage, 4),
        "token_cost_advantage": round(pool.meter.token_cost_advantage, 4),
        "per_tier": per_tier,
        "finish_reasons": _finish_reasons(reqs),
        **_percentiles(latencies),
        **_streaming_metrics(reqs),
    }


def run_pool_continuous(bundles, stream, t_max, n_slots, rng,
                        prefill_chunk=None, prefill_pack=None,
                        walk_bound="live"):
    """3-tier cascade-routed pool: per-tier traffic, tokens/s, TTFT, and KV
    high-water, plus the calls-/token-weighted cost advantage vs routing
    everything to the priciest tier."""
    toks, lens, caps = stream
    mask = (toks != tok.PAD).astype(np.float32)
    policy = _tercile_cascade(toks, mask)
    names = ("small", "medium", "large")
    slot_counts = (n_slots, max(2, 3 * n_slots // 4), max(2, n_slots // 2))
    engines = [_continuous(b, p, t_max, ns, prefill_chunk, prefill_pack,
                           walk_bound)
               for (b, p), ns in zip(bundles, slot_counts)]
    pool = ContinuousPoolEngine(policy, list(zip(names, engines)))
    row = _run_pool_stream(pool, names, engines, stream)
    for name, eng in zip(names, engines):
        row["per_tier"][name]["prefill_compiles"] = \
            eng.stats.prefill_compiles
    return row


def run_long_context(bundle, params, rng, n, t_max, n_slots, smoke):
    """Long-context row: the engine is provisioned for prompts far beyond
    the stream's resident lengths (a wide static page table), while live
    contexts stay within a couple of pages. The same greedy stream runs
    with live-bounded and full-static page walks; the live decode step
    should track the resident context, not ``max_pages_per_slot``."""
    max_seq = 256 if smoke else 512
    lens = rng.integers(6, 17, (n,))
    prompts = [rng.integers(4, tok.VOCAB_SIZE, (l,)).astype(np.int32)
               for l in lens]

    def serve(walk_bound):
        eng = ContinuousEngine(bundle, params, max_new_tokens=t_max,
                               n_slots=n_slots, max_seq=max_seq,
                               walk_bound=walk_bound)
        reqs, delta, wall, t0 = _warm_then_timed(eng, prompts,
                                                 [t_max] * len(prompts))
        return reqs, eng, delta, wall, t0

    reqs_l, live, d_live, wall_live, t0 = serve("live")
    reqs_s, _, d_static, wall_static, _ = serve("static")
    useful = sum(r.n_generated for r in reqs_l)
    latencies = [r.finish_t - t0 for r in reqs_l]
    return {
        "engine": "continuous_paged",
        "requests": n,
        "max_seq": max_seq,
        "max_pages_per_slot": live.cache.max_pages_per_slot,
        # the widest live walk any decode dispatch actually took — the
        # compute analogue of the KV high-water column
        "decode_bound_pages": max(b for b, _ in live._decode_bounds),
        "kv_high_water_bytes": int(live.cache.stats.high_water_pages
                                   * live.cache.bytes_per_page),
        "useful_tokens": useful,
        "wall_s": round(wall_live, 4),
        "tokens_per_s": round(useful / wall_live, 2),
        "step_ms_live": round(1e3 * wall_live
                              / max(d_live["steps"], 1), 3),
        "step_ms_static": round(1e3 * wall_static
                                / max(d_static["steps"], 1), 3),
        "live_step_speedup": round(wall_static / max(wall_live, 1e-9), 3),
        "compiles_timed": d_live["decode_compiles"]
        + d_live["prefill_compiles"],
        "greedy_exact_vs_static": [r.out for r in reqs_l]
        == [r.out for r in reqs_s],
        **_percentiles(latencies),
        **_streaming_metrics(reqs_l),
    }


def run_heavy_admission(bundle, params, rng, n, n_slots, smoke):
    """Heavy-admission row: every request arrives at once with a multi-chunk
    prompt and a tiny output budget, so the engine spends most steps with
    many slots PREFILLING concurrently. Packed dispatch should launch ~one
    prefill kernel per width bucket per step (O(width buckets)) instead of
    one per PREFILLING slot (O(slots), the ``prefill_pack=0`` baseline)."""
    chunk = 8 if smoke else 16
    max_seq = 48 if smoke else 96
    lens = rng.integers(3 * chunk, 5 * chunk + 1, (n,))
    prompts = [rng.integers(4, tok.VOCAB_SIZE, (l,)).astype(np.int32)
               for l in lens]

    def serve(pack):
        eng = ContinuousEngine(bundle, params, max_new_tokens=2,
                               n_slots=n_slots, max_seq=max_seq,
                               prefill_chunk=chunk, prefill_pack=pack)
        reqs, delta, wall, t0 = _warm_then_timed(eng, prompts,
                                                 [2] * len(prompts))
        return reqs, eng, delta, wall, t0

    reqs_p, packed, dp, wall_packed, t0 = serve(None)
    reqs_u, _, du, wall_unpacked, _ = serve(0)
    useful = sum(r.n_generated for r in reqs_p)
    latencies = [r.finish_t - t0 for r in reqs_p]
    widths = {w for l in set(int(x) for x in lens)
              for w in packed.chunk_widths(l)}
    return {
        "engine": "continuous_paged",
        "requests": n,
        "prefill_chunk": chunk,
        "prefill_pack": packed.prefill_pack,
        "kv_high_water_bytes": int(packed.cache.stats.high_water_pages
                                   * packed.cache.bytes_per_page),
        "useful_tokens": useful,
        "wall_s": round(wall_packed, 4),
        "wall_s_unpacked": round(wall_unpacked, 4),
        "tokens_per_s": round(useful / wall_packed, 2),
        "prefill_chunks": dp["prefill_chunks"],
        "prefill_dispatches": dp["prefill_dispatches"],
        "prefill_dispatches_unpacked": du["prefill_dispatches"],
        "prefill_steps": dp["prefill_steps"],
        "prefill_width_buckets": len(widths),
        "prefill_only_steps": dp["prefill_only_steps"],
        "mean_slot_occupancy": round(
            dp["occupancy_sum"] / max(dp["steps"], 1), 2),
        "compiles_timed": dp["prefill_compiles"] + dp["decode_compiles"],
        "greedy_exact_vs_per_slot": [r.out for r in reqs_p]
        == [r.out for r in reqs_u],
        **_percentiles(latencies),
        **_streaming_metrics(reqs_p),
    }


def run_preemption(bundle, params, rng, t_max, smoke):
    """Preemption/robustness row: a tight bounded-queue engine takes a
    low-priority base load, then a high-priority burst plus zero-deadline
    stragglers — driving every degradation path at once (priority
    preemption with recompute-from-pages, bounded-queue load shedding,
    deterministic deadline cancellation). The step-indexed schedule is
    deterministic, so the counters the CI smoke asserts (preemptions > 0,
    sheds > 0, deadline misses > 0, zero leaked pages, preempted outputs
    greedy-exact vs uncontended runs) cannot flake on machine speed."""
    n_base, n_burst, n_doomed = (6, 5, 2) if smoke else (10, 8, 3)
    mk = lambda n: [rng.integers(4, tok.VOCAB_SIZE,
                                 (int(l),)).astype(np.int32)
                    for l in rng.integers(6, 17, (n,))]
    base_p, burst_p, doomed_p = mk(n_base), mk(n_burst), mk(n_doomed)
    eng = ContinuousEngine(bundle, params, max_new_tokens=t_max, n_slots=2,
                           max_seq=48, max_pending=4)
    t0 = time.monotonic()
    base = [eng.submit(p, priority=0) for p in base_p]
    for _ in range(4):   # let the base load occupy the slots mid-decode
        eng.step()
    burst = [eng.submit(p, priority=5) for p in burst_p]
    # outrank the burst so the bounded queue admits them (displacing burst
    # members); their zero deadline then expires them deterministically
    doomed = [eng.submit(p, priority=6, deadline_s=0.0) for p in doomed_p]
    eng.run()
    wall = time.monotonic() - t0
    reqs = base + burst + doomed
    served = [r for r in reqs if r.finish_reason in ("eos", "length",
                                                     "context_cap")]
    useful = sum(r.n_generated for r in served)
    latencies = [r.finish_t - t0 for r in reqs]
    # preempted requests must emit exactly what an uncontended engine emits
    preempted = [r for r in served if r.preemptions > 0]
    exact = True
    for r in preempted:
        ref_eng = ContinuousEngine(bundle, params,
                                   max_new_tokens=r.max_new_tokens,
                                   n_slots=1, max_seq=64)
        ref = ref_eng.submit(r.tokens)
        ref_eng.run()
        exact = exact and r.out == ref.out
    return {
        "engine": "continuous_paged",
        "requests": len(reqs),
        "max_pending": eng.max_pending,
        "useful_tokens": useful,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(useful / wall, 2),
        "preemptions": eng.stats.preemptions,
        "reprefill_tokens": eng.stats.reprefill_tokens,
        "sheds": eng.stats.sheds,
        "deadline_misses": eng.stats.deadline_misses,
        "admission_stalls": eng.stats.admission_stalls,
        "preempted_requests": len(preempted),
        "greedy_exact_preempted": bool(exact and preempted),
        "pages_leaked": int(eng.cache.stats.pages_in_use),
        "all_retired": all(r.done for r in reqs),
        "kv_high_water_bytes": int(eng.cache.stats.high_water_pages
                                   * eng.cache.bytes_per_page),
        "finish_reasons": _finish_reasons(reqs),
        **_percentiles(latencies),
        **_streaming_metrics(served),
    }


def window_ssm_configs(smoke: bool):
    """(plain, window, hybrid) tier configs for the window_ssm row: a
    gemma3-style sliding-window tier and a jamba-style hybrid tier beside a
    plain uniform-global tier — the edge-tier stacks the recurrent-state
    pool and per-layer window masks exist for."""
    base = dict(vocab_size=tok.VOCAB_SIZE, vocab_pad_multiple=16,
                head_dim=16, attn_chunk=32, cache_layout="paged",
                kv_page_size=16)
    plain = ArchConfig(name="ws-plain", family="dense", n_layers=2,
                       d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, **base)
    window = ArchConfig(name="ws-window", family="dense",
                        n_layers=3 if smoke else 6, d_model=64, n_heads=4,
                        n_kv_heads=2, d_ff=128, sliding_window=24,
                        local_global_ratio=2, **base)
    hybrid = ArchConfig(name="ws-hybrid", family="hybrid",
                        n_layers=2 if smoke else 4, d_model=64, n_heads=4,
                        n_kv_heads=2, d_ff=128, attn_every=2, attn_offset=1,
                        moe_every=2, n_experts=4, top_k=2,
                        ssm_state=16, ssm_headdim=16, ssm_chunk=8, **base)
    return plain, window, hybrid


def run_window_ssm(stream, t_max, n_slots, smoke,
                   prefill_chunk=None, prefill_pack=None,
                   walk_bound="live"):
    """window_ssm row: a 3-tier pool whose middle tier is a sliding-window
    stack and whose priciest tier is an SSM/hybrid stack, serving the same
    mixed stream as the other pool row. Greedy-exactness of the two new
    layer kinds is asserted against their dense per-layer reference
    engines on a uniform sub-batch and reported as flags the CI smoke job
    checks."""
    toks, lens, caps = stream
    mask = (toks != tok.PAD).astype(np.float32)
    policy = _tercile_cascade(toks, mask)
    cfgs = window_ssm_configs(smoke)
    names = ("plain", "window", "hybrid")
    bundles = []
    for cfg, seed in zip(cfgs, (1, 4, 5)):
        b = build_model(cfg)
        bundles.append((b, b.init(jax.random.PRNGKey(seed))))
    engines = [_continuous(b, p, t_max, n_slots, prefill_chunk,
                           prefill_pack, walk_bound)
               for b, p in bundles]
    pool = ContinuousPoolEngine(policy, list(zip(names, engines)))
    row = _run_pool_stream(pool, names, engines, stream)
    for name, eng in zip(names, engines):
        row["per_tier"][name]["recurrent_state_bytes"] = \
            eng.rstate.state_bytes if eng.rstate is not None else 0

    # greedy-exactness of the new layer kinds vs the dense per-layer
    # reference engines, on a uniform-length greedy sub-batch
    rng = np.random.default_rng(23)
    exact = {}
    for name, (b, p) in zip(names[1:], bundles[1:]):
        q = rng.integers(4, tok.VOCAB_SIZE, (4, 12)).astype(np.int32)
        rd, ld = Engine(b, p, max_new_tokens=4).serve(q)
        ce = ContinuousEngine(b, p, max_new_tokens=4, n_slots=2, max_seq=96)
        rc, lc = ce.serve(q)
        exact[name] = bool(np.array_equal(rd, rc)
                           and np.array_equal(ld, lc))
    row.update({
        "recurrent_state_bytes": sum(t["recurrent_state_bytes"]
                                     for t in row["per_tier"].values()),
        # widest window-walk start any decode dispatch took (window tier):
        # > 0 means window layers actually skipped dead prefix pages
        "window_pages_start_max": max(ws for _, ws
                                      in engines[1]._decode_bounds),
        "greedy_exact_window": exact["window"],
        "greedy_exact_hybrid": exact["hybrid"],
    })
    return row


def run_speculative(bundle, params, stream, t_max, n_slots, gamma=2,
                    prefill_chunk=None, prefill_pack=None,
                    walk_bound="live"):
    """speculative row: cross-tier speculative decoding on the pool's step
    plane, against the identical non-speculative pool. The draft tier runs
    the SAME weights as the target (self-speculation) — the deterministic
    high-acceptance canary, so the row's acceptance rate and the
    target-steps-per-token < 1 assertion cannot flake on how two random
    tiny models happen to disagree. ``greedy_exact`` asserts byte-identical
    outputs vs the non-speculative pool (the temperature-0 contract)."""
    from repro.serving.faults import StaticPolicy

    toks, lens, caps = stream
    prompts = [toks[i, :lens[i]] for i in range(len(toks))]

    def serve(g):
        engines = [("draft", _continuous(bundle, params, t_max, n_slots,
                                         prefill_chunk, prefill_pack,
                                         walk_bound)),
                   ("target", _continuous(bundle, params, t_max, n_slots,
                                          prefill_chunk, prefill_pack,
                                          walk_bound))]
        pool = ContinuousPoolEngine(StaticPolicy(2, tier=1), engines,
                                    spec_gamma=g)
        target = engines[1][1]
        # warm pass: trace every draft/verify/decode shape the
        # deterministic schedule needs (see _warm_then_timed)
        for p_, c in zip(prompts, caps):
            pool.submit_to("target", p_, int(c))
        pool.run()
        target.cache.stats.high_water_pages = target.cache.stats.pages_in_use
        pool.meter.reset()
        pre = dataclasses.replace(target.stats)
        t0 = time.monotonic()
        reqs = [pool.submit_to("target", p_, int(c))
                for p_, c in zip(prompts, caps)]
        pool.run()
        wall = time.monotonic() - t0
        delta = {f.name: getattr(target.stats, f.name) - getattr(pre, f.name)
                 for f in dataclasses.fields(target.stats)
                 if isinstance(getattr(target.stats, f.name), int)}
        return pool, target, reqs, delta, wall, t0

    pool, target, reqs, d, wall, t0 = serve(gamma)
    _, _, base_reqs, d0, base_wall, _ = serve(0)
    useful = sum(r.n_generated for r in reqs)
    latencies = [r.finish_t - t0 for r in reqs]
    # the acceptance criterion: launches the target tier paid per emitted
    # token (plain decode steps + verify chunks, over the timed stream) —
    # strictly < 1.0 is the whole point of drafting on the cheap tier
    target_steps = d["decode_steps"] + d["verify_steps"]
    meter = pool.meter.summary()
    return {
        "engine": "continuous_paged_pool_speculative",
        "requests": len(reqs),
        "gamma": gamma,
        "useful_tokens": useful,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(useful / wall, 2),
        "tokens_per_s_nonspec": round(
            sum(r.n_generated for r in base_reqs) / base_wall, 2),
        "spec_rounds": d["spec_rounds"],
        "spec_fallbacks": d["spec_fallbacks"],
        "drafted_tokens": d["drafted_tokens"],
        "accepted_tokens": d["accepted_tokens"],
        "rejected_tokens": d["rejected_tokens"],
        "acceptance_rate": round(
            d["accepted_tokens"] / max(d["drafted_tokens"], 1), 4),
        "target_steps_per_token": round(
            target_steps / max(d["decode_tokens"], 1), 4),
        "draft_steps": d["draft_steps"],
        "verify_steps": d["verify_steps"],
        "decode_steps": d["decode_steps"],
        "decode_steps_nonspec": d0["decode_steps"],
        "meter_drafted_draft_tier": meter["draft"]["drafted"],
        "meter_accepted_target_tier": meter["target"]["accepted"],
        "meter_rejected_target_tier": meter["target"]["rejected"],
        "greedy_exact": [r.out for r in reqs]
        == [r.out for r in base_reqs],
        "kv_high_water_bytes": int(target.cache.stats.high_water_pages
                                   * target.cache.bytes_per_page),
        "finish_reasons": _finish_reasons(reqs),
        **_percentiles(latencies),
        **_streaming_metrics(reqs),
    }


def run_escalation(bundles, stream, t_max, n_slots,
                   prefill_chunk=None, prefill_pack=None,
                   walk_bound="live"):
    """escalation row: mid-stream quality escalation on a 2-tier pool.
    Every request lands on the cheap tier; an observe-only pass records
    each stream's peak decode uncertainty and the abort threshold is the
    median peak (``calibrate_abort_threshold`` at a 50% budget), so the
    stream achieving the max peak is guaranteed to cross it in the live
    pass — the escalation count cannot flake to zero. Crossed streams are
    cancelled (pages freed, prompt + emitted prefix kept) and re-admitted
    one tier up as ONE chunked prefill; the row asserts the continuation
    is byte-identical to the upper tier decoding greedily from that same
    prefix, that the token split across tiers sums exactly to the useful
    tokens, and that per-boundary cascade gates with identical heads
    reproduce the legacy shared-score cascade on this stream's scores."""
    from repro.core.thresholds import calibrate_abort_threshold
    from repro.serving.engine import EscalationMonitor
    from repro.serving.faults import StaticPolicy

    (bs, ps_), (bl, pl_) = bundles
    toks, lens, caps = stream
    prompts = [toks[i, :lens[i]] for i in range(len(toks))]
    caps_i = [int(c) for c in caps]

    def mk_pool(mon):
        engines = [("small", _continuous(bs, ps_, t_max, n_slots,
                                         prefill_chunk, prefill_pack,
                                         walk_bound)),
                   ("large", _continuous(bl, pl_, t_max,
                                         max(2, n_slots // 2),
                                         prefill_chunk, prefill_pack,
                                         walk_bound))]
        return ContinuousPoolEngine(StaticPolicy(2, tier=0), engines,
                                    escalation=[mon])

    # observe-only pass: peaks without cancelling anyone
    obs = mk_pool(EscalationMonitor(abort_threshold=None, min_tokens=1))
    obs_reqs = [obs.submit_to("small", p_, c)
                for p_, c in zip(prompts, caps_i)]
    obs.run()
    peaks = [r.esc_peak_score for r in obs_reqs if r.esc_peak_score > 0]
    thr = calibrate_abort_threshold(peaks, 0.5)

    # min_tokens=1: a stream whose observed peak crossed thr replays the
    # identical greedy prefix live, so it escalates at the same step
    pool = mk_pool(EscalationMonitor(abort_threshold=thr, min_tokens=1))
    small, large = pool.engines
    # warm pass traces every shape the deterministic schedule needs —
    # including the upper tier's continuation prefills
    for p_, c in zip(prompts, caps_i):
        pool.submit_to("small", p_, c)
    pool.run()
    warm_log = [(ft, tt, k) for _, ft, tt, k in pool.escalation_log]
    pool.escalation_log.clear()
    for eng in (small, large):
        eng.cache.stats.high_water_pages = eng.cache.stats.pages_in_use
    pool.meter.reset()
    t0 = time.monotonic()
    reqs = [pool.submit_to("small", p_, c)
            for p_, c in zip(prompts, caps_i)]
    pool.run()
    wall = time.monotonic() - t0

    # every continuation must be byte-identical to the upper tier decoding
    # greedily, uncontended, from (prompt + the emitted prefix)
    by_rid = {r.rid: i for i, r in enumerate(reqs)}
    exact = bool(pool.escalation_log)
    for rid, ft, tt, k in pool.escalation_log:
        i = by_rid[rid]
        r = reqs[i]
        ref_eng = _continuous(bl, pl_, t_max, 2, prefill_chunk,
                              prefill_pack, walk_bound)
        ref = ref_eng.submit(
            np.concatenate([prompts[i], np.asarray(r.out[:k], np.int32)]),
            max_new_tokens=max(len(r.out) - k, 1))
        ref_eng.run()
        exact = exact and r.out[k:] == ref.out[:len(r.out) - k]

    # tentpole parity: per-boundary gates with identical heads == the
    # legacy shared-score cascade, on this stream's real router scores
    mask = (toks != tok.PAD).astype(np.float32)
    r_, scores = _toy_router(toks, mask)
    ts = (float(np.quantile(scores, 2 / 3)),
          float(np.quantile(scores, 1 / 3)))
    shared = CascadePolicy(r_, ts)
    per_b = CascadePolicy(boundaries=tuple(r_.with_threshold(t)
                                           for t in ts))
    tier_s, score_s = shared.decide(toks, mask)
    tier_b, score_b = per_b.decide(toks, mask)
    parity = bool(np.array_equal(tier_s, tier_b)
                  and np.allclose(score_s, score_b))

    meter = pool.meter.summary()
    useful = sum(r.n_generated for r in reqs)
    latencies = [r.finish_t - t0 for r in reqs]
    return {
        "engine": "continuous_paged_pool_escalation",
        "requests": len(reqs),
        "abort_threshold": round(float(thr), 4),
        "escalate_frac_budget": 0.5,
        "escalations": len(pool.escalation_log),
        "escalations_deterministic": warm_log
        == [(ft, tt, k) for _, ft, tt, k in pool.escalation_log],
        "meter_escalations_small": meter["small"]["escalations"],
        "esc_tokens_small": meter["small"]["esc_tokens"],
        # the CALL never splits: calls_small counts only streams that
        # FINISHED on the cheap tier (§2.3 cost metrics undiluted)
        "calls_small": meter["small"]["calls"],
        "calls_large": meter["large"]["calls"],
        "gen_tokens_small": meter["small"]["gen_tokens"],
        "gen_tokens_large": meter["large"]["gen_tokens"],
        "token_split_exact": meter["small"]["gen_tokens"]
        + meter["large"]["gen_tokens"] == useful,
        "greedy_exact_continuations": exact,
        "per_boundary_matches_shared": parity,
        "useful_tokens": useful,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(useful / wall, 2),
        "kv_high_water_bytes": int(
            small.cache.stats.high_water_pages * small.cache.bytes_per_page
            + large.cache.stats.high_water_pages
            * large.cache.bytes_per_page),
        "pages_leaked": int(small.cache.stats.pages_in_use
                            + large.cache.stats.pages_in_use),
        "finish_reasons": _finish_reasons(reqs),
        **_percentiles(latencies),
        **_streaming_metrics(reqs),
    }


def run_prefix_sharing(bundle, params, smoke):
    """prefix_sharing row: multi-turn chat + best-of-N fan-out replay on a
    shared-prefix (copy-on-write radix tree) engine vs the identical stream
    with ``prefix_cache=0``. Multi-turn sessions submit in turn waves —
    every turn's prompt is the full history (previous prompt + outputs +
    new user text), the regime where retirement-published pages make the
    next turn's prefill nearly free; the fan-out phase drains one leader,
    then N followers sharing its system prompt land concurrently (pages
    shared across live slots — the ``pages_shared_high_water`` column).
    The schedule runs twice per engine (warm pass traces shapes, the tree
    is cleared between passes so the timed pass rediscovers every hit) and
    the row reports hit rate, prefill tokens saved, TTFT p99 on vs off,
    COW splits, and the refcount zero-leak audit CI asserts."""
    S, T, F = (2, 3, 4) if smoke else (4, 4, 6)
    # system prompts deliberately NOT page-multiples (page_size=16): the
    # leader's published tail page then mixes system + suffix tokens, so
    # followers fork mid-page and the row exercises the COW split path
    user_len, out_cap, sys_len, sfx_len = (16, 4, 40, 8) if smoke \
        else (24, 6, 72, 8)
    max_seq = 128 if smoke else 192
    budget = 32 if smoke else 64
    rng = np.random.default_rng(23)
    sys_chat = rng.integers(4, tok.VOCAB_SIZE, (sys_len,)).astype(np.int32)
    users = rng.integers(4, tok.VOCAB_SIZE,
                         (S, T, user_len)).astype(np.int32)
    sys_fan = rng.integers(4, tok.VOCAB_SIZE, (sys_len,)).astype(np.int32)
    sfx = rng.integers(4, tok.VOCAB_SIZE,
                       (F + 1, sfx_len)).astype(np.int32)

    def schedule(eng):
        reqs = []
        hist = [np.asarray(sys_chat) for _ in range(S)]
        for t in range(T):
            wave = []
            for s in range(S):
                hist[s] = np.concatenate([hist[s], users[s, t]])
                wave.append(eng.submit(hist[s], max_new_tokens=out_cap))
            eng.run()
            for s, r in enumerate(wave):
                hist[s] = np.concatenate(
                    [hist[s], np.asarray(r.out, np.int32)])
            reqs.extend(wave)
        # best-of-N fan-out: the leader drains first (publishing its system
        # prompt), then the followers land concurrently and share it
        leader = eng.submit(np.concatenate([sys_fan, sfx[0]]),
                            max_new_tokens=out_cap)
        eng.run()
        reqs.append(leader)
        wave = [eng.submit(np.concatenate([sys_fan, sfx[i + 1]]),
                           max_new_tokens=out_cap) for i in range(F)]
        eng.run()
        reqs.extend(wave)
        return reqs

    def serve(prefix):
        eng = ContinuousEngine(bundle, params, max_new_tokens=out_cap,
                               n_slots=4, max_seq=max_seq,
                               prefix_cache=prefix)
        schedule(eng)                # warm: trace every shape (greedy, so
        if eng.cache.prefix is not None:   # the replay is identical)
            eng.cache.prefix.clear()       # timed pass rediscovers hits
        eng.cache.stats.high_water_pages = eng.cache.stats.pages_in_use
        eng.cache.stats.high_water_shared = 0
        pre = dataclasses.replace(eng.stats)
        tpre = dataclasses.replace(eng.cache.prefix.stats) \
            if eng.cache.prefix is not None else None
        t0 = time.monotonic()
        reqs = schedule(eng)
        wall = time.monotonic() - t0
        delta = {f.name: getattr(eng.stats, f.name) - getattr(pre, f.name)
                 for f in dataclasses.fields(eng.stats)
                 if isinstance(getattr(eng.stats, f.name), int)}
        if tpre is not None:
            ts = eng.cache.prefix.stats
            delta.update(published_pages=ts.published_pages
                         - tpre.published_pages,
                         evicted_pages=ts.evicted_pages - tpre.evicted_pages)
        return eng, reqs, delta, wall, t0

    eng_on, reqs_on, d_on, wall_on, t0_on = serve(budget)
    eng_off, reqs_off, d_off, wall_off, _ = serve(0)
    useful = sum(r.n_generated for r in reqs_on)
    latencies = [r.finish_t - t0_on for r in reqs_on]
    # the refcount zero-leak audit CI asserts: post-drain, every page is
    # free-list or tree-resident and every count matches its references
    c = eng_on.cache
    resident = c.prefix.resident
    clean = not c.check_refcounts() \
        and len(c._free) == c.num_pages - 1 - resident
    saved = 1.0 - d_on["prefill_tokens"] / max(d_off["prefill_tokens"], 1)
    return {
        "engine": "continuous_paged_prefix",
        "requests": len(reqs_on),
        "sessions": S, "turns": T, "fanout": F,
        "prefix_cache_pages": budget,
        "useful_tokens": useful,
        "wall_s": round(wall_on, 4),
        "wall_s_nonshared": round(wall_off, 4),
        "tokens_per_s": round(useful / wall_on, 2),
        **_percentiles(latencies),
        **_streaming_metrics(reqs_on),
        "ttft_p99_nonshared_s": _streaming_metrics(reqs_off)["ttft_p99_s"],
        "prefill_tokens": d_on["prefill_tokens"],
        "prefill_tokens_nonshared": d_off["prefill_tokens"],
        "prefill_tokens_saved_frac": round(saved, 4),
        "prefill_dispatches": d_on["prefill_dispatches"],
        "prefill_dispatches_nonshared": d_off["prefill_dispatches"],
        "prefix_hits": d_on["prefix_hits"],
        "prefix_misses": d_on["prefix_misses"],
        "hit_rate": round(d_on["prefix_hits"]
                          / max(d_on["prefix_hits"]
                                + d_on["prefix_misses"], 1), 4),
        "prefix_hit_tokens": d_on["prefix_hit_tokens"],
        "prefix_hit_pages": d_on["prefix_hit_pages"],
        "cow_splits": d_on["cow_splits"],
        "published_pages": d_on["published_pages"],
        "evicted_pages": d_on["evicted_pages"],
        "pages_shared_high_water": c.stats.high_water_shared,
        "tree_resident_pages": resident,
        "greedy_exact": [r.out for r in reqs_on]
        == [r.out for r in reqs_off],
        "refcount_clean": bool(clean),
        "pages_leaked": int(c.stats.pages_in_use - resident),
        "kv_high_water_bytes": int(c.stats.high_water_pages
                                   * c.bytes_per_page),
        "finish_reasons": _finish_reasons(reqs_on),
    }


def check_padding_parity(bundle, params, rng):
    """Dense Engine.serve, ContinuousEngine.serve, and
    ContinuousPoolEngine.serve must agree elementwise on greedy responses —
    including the tok.PAD padding of every row's tail. Emitted into the
    JSON so the CI smoke job asserts it without a separate harness."""
    q = rng.integers(4, tok.VOCAB_SIZE, (4, 8)).astype(np.int32)
    mask = np.ones_like(q, np.float32)
    dense = Engine(bundle, params, max_new_tokens=4)
    rd, ld = dense.serve(q)
    ce = ContinuousEngine(bundle, params, max_new_tokens=4, n_slots=2,
                          max_seq=32)
    rc, _ = ce.serve(q)
    c2 = ContinuousEngine(bundle, params, max_new_tokens=4, n_slots=2,
                          max_seq=32)
    router, _ = _toy_router(q, mask)
    pool = ContinuousPoolEngine(ThresholdPolicy(router.with_threshold(-1.0)),
                                [("a", c2), ("b", c2)])
    res = pool.serve(q, mask)
    return bool(np.array_equal(rd, rc)
                and np.array_equal(rc, res.responses)
                and np.array_equal(ld, res.lengths)
                and all((res.responses[i, l:] == tok.PAD).all()
                        for i, l in enumerate(res.lengths)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny models + short stream (CI perf canary)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill width for the continuous engines "
                         "(0 = one-shot; default: the config's knob)")
    ap.add_argument("--prefill-pack", type=int, default=None,
                    help="max PREFILLING slots stacked per prefill kernel "
                         "launch (0 = per-slot dispatch; default: n_slots)")
    ap.add_argument("--walk-bound", choices=("live", "static"),
                    default="live",
                    help="bound paged kernels' page walks by the live max "
                         "context (live) or the static table width (static)")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: repo-root "
                         "BENCH_serving.json; --smoke defaults to no file)")
    args = ap.parse_args()
    enable_compile_cache()

    n = args.requests or (12 if args.smoke else 64)
    t_max = 8 if args.smoke else 32
    batch = 8 if args.smoke else 16
    n_slots = 4 if args.smoke else 8
    rng = np.random.default_rng(0)
    stream = make_stream(rng, n, t_max)

    cfg_s, cfg_m, cfg_l = tier_configs(args.smoke)
    pool_bundles = []
    for cfg, seed in ((cfg_s, 1), (cfg_m, 3), (cfg_l, 2)):
        b = build_model(cfg)
        pool_bundles.append((b, b.init(jax.random.PRNGKey(seed))))
    bundles = [pool_bundles[0], pool_bundles[2]]   # the hybrid (S, L) pair

    results = {"config": {"requests": n, "t_max": t_max, "batch": batch,
                          "n_slots": n_slots, "smoke": args.smoke,
                          "prefill_chunk": args.prefill_chunk,
                          "prefill_pack": args.prefill_pack,
                          "walk_bound": args.walk_bound,
                          "small": cfg_s.name, "medium": cfg_m.name,
                          "large": cfg_l.name},
               "tiers": {}}

    def report(name, r):
        ttft = f"ttft p99 {r['ttft_p99_s']:.2f}s"
        itk = f"  itk p99 {r['intertoken_p99_s'] * 1e3:.0f}ms" \
            if "intertoken_p99_s" in r else ""
        print(f"  {name:<10} {r['tokens_per_s']:>8} tok/s  "
              f"p99 {r['p99_s']:.2f}s  {ttft}{itk}  "
              f"kv {r['kv_high_water_bytes']}")

    for tier, (bundle, params) in (("small", bundles[0]),
                                   ("large", bundles[1])):
        print(f"== {tier} ==")
        d = run_dense(bundle, params, stream, t_max, batch)
        c = run_continuous(bundle, params, stream, t_max, n_slots,
                           np.random.default_rng(7), args.prefill_chunk,
                           args.prefill_pack, args.walk_bound)
        results["tiers"][tier] = {"dense": d, "continuous": c}
        report("dense", d)
        report("continuous", c)

    print("== hybrid ==")
    d = run_hybrid_dense(bundles, stream, t_max, batch)
    c = run_hybrid_continuous(bundles, stream, t_max, n_slots,
                              np.random.default_rng(7), args.prefill_chunk,
                              args.prefill_pack, args.walk_bound)
    results["tiers"]["hybrid"] = {"dense": d, "continuous": c}
    report("dense", d)
    report("continuous", c)

    speedup = c["tokens_per_s"] / max(d["tokens_per_s"], 1e-9)
    kv_ratio = c["kv_high_water_bytes"] / max(d["kv_high_water_bytes"], 1)
    results["hybrid_speedup"] = round(speedup, 3)
    results["hybrid_kv_ratio"] = round(kv_ratio, 3)
    print(f"hybrid: {speedup:.2f}x tokens/s, {kv_ratio:.2f}x KV high-water")

    print("== pool (3-tier cascade) ==")
    p = run_pool_continuous(pool_bundles, stream, t_max, n_slots,
                            np.random.default_rng(7), args.prefill_chunk,
                            args.prefill_pack, args.walk_bound)
    results["pool"] = p
    report("pool", p)
    for name, row in p["per_tier"].items():
        print(f"    {name:<8} {row['calls']:>4} calls  "
              f"{row['tokens_per_s']:>8} tok/s  kv "
              f"{row['kv_high_water_bytes']}")
    print(f"pool: {p['cost_advantage']:.0%} of calls / "
          f"{p['token_cost_advantage']:.0%} of tokens off {cfg_l.name}")

    print("== long context (live-bounded walks) ==")
    lc = run_long_context(bundles[0][0], bundles[0][1],
                          np.random.default_rng(11), n, t_max, n_slots,
                          args.smoke)
    results["long_context"] = lc
    report("long-ctx", lc)
    print(f"    step {lc['step_ms_live']}ms live vs "
          f"{lc['step_ms_static']}ms static "
          f"({lc['live_step_speedup']:.2f}x; widest live walk "
          f"{lc['decode_bound_pages']} of {lc['max_pages_per_slot']} "
          f"pages)")

    print("== window_ssm (3-tier: plain + sliding-window + hybrid) ==")
    ws = run_window_ssm(stream, t_max, n_slots, args.smoke,
                        args.prefill_chunk, args.prefill_pack,
                        args.walk_bound)
    results["window_ssm"] = ws
    report("window-ssm", ws)
    for name, row in ws["per_tier"].items():
        rec = f"  rec {row['recurrent_state_bytes']}" \
            if row["recurrent_state_bytes"] else ""
        print(f"    {name:<8} {row['calls']:>4} calls  "
              f"{row['tokens_per_s']:>8} tok/s  kv "
              f"{row['kv_high_water_bytes']}{rec}")
    print(f"    greedy-exact: window {ws['greedy_exact_window']}, "
          f"hybrid {ws['greedy_exact_hybrid']}; widest window walk start "
          f"page {ws['window_pages_start_max']}")

    print("== heavy admission (packed prefill) ==")
    ha = run_heavy_admission(bundles[0][0], bundles[0][1],
                             np.random.default_rng(13), n, n_slots,
                             args.smoke)
    results["heavy_admission"] = ha
    report("heavy-adm", ha)
    print(f"    {ha['prefill_dispatches']} packed dispatches for "
          f"{ha['prefill_chunks']} slot-chunks over "
          f"{ha['prefill_steps']} prefill steps "
          f"(per-slot baseline: {ha['prefill_dispatches_unpacked']})")

    print("== preemption (priority burst on a tight bounded queue) ==")
    pr = run_preemption(bundles[0][0], bundles[0][1],
                        np.random.default_rng(17), t_max, args.smoke)
    results["preemption"] = pr
    report("preemption", pr)
    print(f"    {pr['preemptions']} preemptions "
          f"({pr['reprefill_tokens']} re-prefill tokens), "
          f"{pr['sheds']} sheds, {pr['deadline_misses']} deadline misses; "
          f"preempted greedy-exact {pr['greedy_exact_preempted']}, "
          f"{pr['pages_leaked']} pages leaked, "
          f"queue p99 {pr['queue_p99_s']:.2f}s")

    print("== speculative (cross-tier drafting, self-spec canary) ==")
    sp = run_speculative(bundles[1][0], bundles[1][1], stream, t_max,
                         n_slots, 2, args.prefill_chunk, args.prefill_pack,
                         args.walk_bound)
    results["speculative"] = sp
    report("speculative", sp)
    print(f"    gamma={sp['gamma']}: {sp['acceptance_rate']:.0%} acceptance "
          f"over {sp['drafted_tokens']} drafted "
          f"({sp['spec_rounds']} rounds), "
          f"{sp['target_steps_per_token']:.2f} target steps/token "
          f"(non-spec baseline 1.0), greedy-exact {sp['greedy_exact']}; "
          f"{sp['tokens_per_s']} vs {sp['tokens_per_s_nonspec']} tok/s "
          "non-spec")

    print("== escalation (mid-stream quality escalation, 2-tier) ==")
    es = run_escalation(bundles, stream, t_max, n_slots,
                        args.prefill_chunk, args.prefill_pack,
                        args.walk_bound)
    results["escalation"] = es
    report("escalation", es)
    print(f"    {es['escalations']} of {es['requests']} streams escalated "
          f"(abort threshold {es['abort_threshold']}); "
          f"continuations greedy-exact {es['greedy_exact_continuations']}, "
          f"token split {es['gen_tokens_small']}+{es['gen_tokens_large']} "
          f"exact {es['token_split_exact']}, per-boundary == shared "
          f"{es['per_boundary_matches_shared']}, "
          f"{es['pages_leaked']} pages leaked")

    print("== prefix sharing (multi-turn chat + best-of-N fan-out) ==")
    px = run_prefix_sharing(bundles[0][0], bundles[0][1], args.smoke)
    results["prefix_sharing"] = px
    report("prefix", px)
    print(f"    {px['prefix_hit_tokens']} prefill tokens skipped "
          f"({px['prefill_tokens_saved_frac']:.0%} saved vs "
          f"prefix_cache=0; hit rate {px['hit_rate']:.0%}), "
          f"ttft p99 {px['ttft_p99_s']:.2f}s vs "
          f"{px['ttft_p99_nonshared_s']:.2f}s non-shared, "
          f"{px['pages_shared_high_water']} pages shared high-water, "
          f"{px['cow_splits']} cow splits; greedy-exact "
          f"{px['greedy_exact']}, refcounts clean {px['refcount_clean']}")

    results["padding_parity"] = check_padding_parity(
        bundles[0][0], bundles[0][1], np.random.default_rng(19))
    print(f"padding parity across serve paths: {results['padding_parity']}")

    out = args.out
    if out is None and not args.smoke:
        out = os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_serving.json")
    if out:
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {os.path.abspath(out)}")


if __name__ == "__main__":
    main()
