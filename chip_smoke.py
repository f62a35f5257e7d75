#!/usr/bin/env python3
"""Chip smoke: the paper's routed serving path on one TPU, at published widths.

Serves a small/large pair through the normal library entry points —
``make_engine`` per tier, ``ContinuousPoolEngine(ThresholdPolicy(
HybridRouter(...)))`` over them, as ``repro.launch.serve`` builds it:

  tier 0  mamba2-130m  (24 SSD layers, d_model 768; about 0.13 B params)
  tier 1  gemma3-4b    (34 layers, 5:1 sliding-window, GQA; 3.88 B params)

Both tiers run at full width and depth in bfloat16 with the Pallas kernels
and the paged KV pool (8 slots, 2048-token slots). Weights are random, made
from ``--seed`` directly in bfloat16 under ``jit``; the router is the
launcher's ``RouterConfig``, also random, gated at the median score of the
smoke's prompts so both tiers get traffic. About 16 synthetic requests are
served to completion. The run fails (non-zero exit, no result line) when:

  * JAX's backend is not a TPU (checked first; there is no CPU fallback);
  * a paged decode, paged prefill or SSD kernel run on the chip at these
    widths disagrees with its float32 ``precision="highest"`` reference
    beyond a bfloat16 tolerance;
  * a request is not retired with finish reason "eos" or "length";
  * per-tier calls do not sum to the requests, or a tier served none;
  * a tier's page refcount audit is not clean;
  * a compiled prefill or decode step that should hold a kernel lacks a
    ``tpu_custom_call`` (the kernel was interpreted or replaced by jnp).

The last line of standard output is one JSON object naming the device.
Compiled programs persist in JAX's compilation cache (see
``repro.launch.compile_cache``). This is a smoke run, not a benchmark: its
seconds include compilation and are not speed.

Usage: python3 chip_smoke.py [--seed N] [--requests N] [--new-tokens N]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SLOTS, MAX_SEQ = 8, 2048
TIERS = ("mamba2-130m", "gemma3-4b")   # cheapest -> priciest
FINISHED = ("eos", "length")


class Failure(Exception):
    """A check of the smoke did not hold."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise Failure(what)


class _FirstCallSpec:
    """Wraps an engine's jitted step and keeps the abstract arguments of its
    first call, so that call's program can be compiled again afterwards and
    its text inspected."""

    def __init__(self, fn):
        self.fn, self.spec = fn, None

    def __call__(self, *args):
        import jax
        if self.spec is None:
            self.spec = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding)
                if isinstance(a, jax.Array) else a, args)
        return self.fn(*args)

    def compiled_text(self) -> str:
        return self.fn.lower(*self.spec).compile().as_text()


def _kernel_checks(seed: int) -> None:
    """Each main-path kernel, compiled on the chip at the smoke's widths,
    against its reference in float32 at the highest matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.kernels.paged_decode_attention.kernel import \
        paged_decode_attention_gqa
    from repro.kernels.paged_decode_attention.ref import \
        paged_decode_attention_ref
    from repro.kernels.paged_prefill_attention.kernel import \
        paged_prefill_attention_gqa
    from repro.kernels.paged_prefill_attention.ref import \
        paged_prefill_attention_ref
    from repro.kernels.ssd_scan.kernel import ssd_chunk_scan
    from repro.kernels.ssd_scan.ref import ssd_chunk_ref

    g, m = get_config("gemma3-4b"), get_config("mamba2-130m")
    K, D = g.n_kv_heads, g.resolved_head_dim
    G, ps = g.n_heads // K, g.kv_page_size
    B, MP, C = SLOTS, 8, 16
    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16

    def rand(shape, dtype=bf, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    def f32(*xs):
        return [x.astype(jnp.float32) for x in xs]

    def close(name, out, ref):
        out = np.asarray(out, np.float32)
        ref = np.asarray(ref, np.float32)
        _check(bool(np.isfinite(out).all()), f"{name}: non-finite output")
        err = float(np.abs(out - ref).max())
        tol = 2e-2 * max(1.0, float(np.abs(ref).max()))
        print(f"kernel {name}: shape {out.shape} max|err| {err:.3e} "
              f"(tolerance {tol:.3e})")
        _check(err <= tol, f"{name} disagrees with its reference: max "
                           f"|err| {err:.3e} > {tol:.3e}")

    # stacked pools of a few layers, as the serving step holds them; the
    # kernels read one layer > 0 named by a traced scalar, as under the
    # step's layer scan. Each slot owns MP distinct pages (0 = scratch)
    L, layer = 3, 2
    shape = (L, 1 + B * MP, K, ps, D)
    kp, vp = rand(shape), rand(shape)
    k1, v1 = kp[layer], vp[layer]
    li = jnp.int32(layer)
    pt = jnp.asarray(1 + np.arange(B * MP).reshape(B, MP), jnp.int32)
    lens = jnp.asarray(rng.integers(1, MP * ps + 1, B), jnp.int32)
    q = rand((B, K, G, D), scale=D ** -0.5)
    with jax.default_matmul_precision("highest"):
        ref = paged_decode_attention_ref(*f32(q, k1, v1), pt, lens)
        ref_w = paged_decode_attention_ref(*f32(q, k1, v1), pt, lens,
                                           window=ps + 3)
    decode = jax.jit(paged_decode_attention_gqa, static_argnames="window")
    close("paged_decode", decode(q, kp, vp, li, pt, lens), ref)
    close("paged_decode(window)",
          decode(q, kp, vp, li, pt, lens, window=ps + 3), ref_w)

    total = jnp.asarray(rng.integers(C, MP * ps + 1, B), jnp.int32)
    start = total - C
    qc = rand((B, K, C, G, D), scale=D ** -0.5)
    with jax.default_matmul_precision("highest"):
        ref = paged_prefill_attention_ref(*f32(qc, k1, v1), pt, start, total)
    close("paged_prefill", jax.jit(paged_prefill_attention_gqa)(
        qc, kp, vp, li, pt, start, total), ref)

    H, P, N = m.ssm_nheads, m.ssm_headdim, m.ssm_state
    for chunk in (C, m.ssm_chunk):   # the serving chunk, the model's chunk
        bc = 2
        x = rand((bc, H, chunk, P))
        dt = jnp.asarray(rng.uniform(1e-3, 0.1, (bc, H, chunk, 1)),
                         jnp.float32)
        dacum = jnp.cumsum(-dt * jnp.asarray(
            rng.uniform(1.0, 16.0, (1, H, 1, 1)), jnp.float32), axis=2)
        Bm, Cm = rand((bc, chunk, N), scale=0.3), rand((bc, chunk, N),
                                                       scale=0.3)
        with jax.default_matmul_precision("highest"):
            ry, rs = ssd_chunk_ref(*f32(x, dt, dacum, Bm, Cm))
        y, st = ssd_chunk_scan(x, dt, dacum, Bm, Cm)
        close(f"ssd_chunk_scan(l={chunk}) y", y, ry)
        close(f"ssd_chunk_scan(l={chunk}) state", st, rs)


def _compile_counter():
    """Counts XLA backend compiles and their seconds, and persistent
    compilation-cache hits, from JAX's own monitoring events."""
    import jax
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


def _serve(args) -> None:
    import dataclasses

    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.core import HybridRouter, ThresholdPolicy
    from repro.data import tokenizer as tok
    from repro.data.tasks import generate_dataset
    from repro.models import RouterConfig, build_model, init_router_encoder
    from repro.serving import (ContinuousEngine, ContinuousPoolEngine,
                               make_engine)

    key = jax.random.PRNGKey(args.seed)
    engines, recorders = [], []
    for t, arch in enumerate(TIERS):
        cfg = dataclasses.replace(get_config(arch), dtype="bfloat16",
                                  use_pallas=True, cache_layout="paged")
        bundle = build_model(cfg)
        t0 = time.perf_counter()
        # initialised on the device in bfloat16: no float32 copy of the
        # weights exists at any point
        params = jax.block_until_ready(
            jax.jit(bundle.init)(jax.random.fold_in(key, t)))
        n = sum(a.size for a in jax.tree_util.tree_leaves(params))
        print(f"tier {t} {arch}: {n:,} params "
              f"({2 * n / 1e9:.2f} GB bf16), init {time.perf_counter() - t0:.1f} s")
        eng = make_engine(bundle, params, max_new_tokens=args.new_tokens,
                          n_slots=SLOTS, max_seq=MAX_SEQ)
        _check(isinstance(eng, ContinuousEngine),
               f"{arch}: make_engine did not build the continuous engine")
        rec = {"decode": _FirstCallSpec(eng._decode),
               "prefill": _FirstCallSpec(eng._prefill_chunk_fn)}
        eng._decode, eng._prefill_chunk_fn = rec["decode"], rec["prefill"]
        engines.append((arch, eng))
        recorders.append(rec)
        print(f"tier {t} {arch}: KV pool "
              f"{eng.cache.num_pages * eng.cache.bytes_per_page / 1e9:.2f} GB"
              f" ({eng.cache.num_pages} pages of {eng.cache.page_size})")

    rcfg = RouterConfig(vocab_size=tok.VOCAB_SIZE, n_layers=2, d_model=64,
                        n_heads=4, d_ff=256)
    rparams = init_router_encoder(jax.random.fold_in(key, len(TIERS)), rcfg)
    ds = generate_dataset(np.random.default_rng(args.seed), args.requests)
    scores = np.asarray(HybridRouter(rparams, rcfg, 0.0).scores(
        ds.query, ds.query_mask))
    router = HybridRouter(rparams, rcfg, float(np.median(scores)))
    pool = ContinuousPoolEngine(ThresholdPolicy(router), engines)

    t0 = time.perf_counter()
    reqs, tiers, _ = pool.submit(ds.query, ds.query_mask)
    pool.run()
    wall = time.perf_counter() - t0

    bad = [(r.rid, r.finish_reason) for r in reqs
           if not r.done or r.finish_reason not in FINISHED]
    _check(not bad, f"requests not retired with {FINISHED}: {bad}")
    summary = pool.meter.summary()
    calls = [summary[arch]["calls"] for arch in TIERS]
    for arch in TIERS:
        row = summary[arch]
        print(f"tier {arch}: {row['calls']} calls, {row['gen_tokens']} "
              "generated tokens")
    _check(sum(calls) == len(reqs),
           f"per-tier calls {calls} do not sum to {len(reqs)} requests")
    _check(min(calls) > 0, f"a tier served no request: calls {calls}")
    _check(list(np.bincount(tiers, minlength=len(TIERS))) == calls,
           f"routing {np.bincount(tiers)} disagrees with the meter {calls}")
    for arch, eng in engines:
        leaks = eng.cache.check_refcounts()
        _check(not leaks, f"{arch}: page refcount audit: {leaks[:3]}")
    print(f"served {len(reqs)} requests in {wall:.1f} s wall "
          "(compilation included)")

    # the steps that must hold a kernel: gemma3's paged prefill and decode,
    # mamba2's SSD prefill (its decode step is a recurrence with no kernel)
    wants = [(0, "prefill"), (1, "prefill"), (1, "decode")]
    for t, step in wants:
        spec = recorders[t][step]
        _check(spec.spec is not None, f"{TIERS[t]} {step}: never ran")
        text = spec.compiled_text()
        _check("tpu_custom_call" in text,
               f"{TIERS[t]} {step}: compiled step holds no tpu_custom_call")
        print(f"tier {TIERS[t]} {step}: compiled step holds "
              f"{text.count('tpu_custom_call')} tpu_custom_call op(s)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is {backend!r}); "
              "this smoke runs only on a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}")
    tally = _compile_counter()
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    try:
        _kernel_checks(args.seed)
        _serve(args)
    except Failure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"compiles: {tally['compiles']} ({tally['compile_s']:.1f} s), "
          f"persistent cache hits: {tally['cache_hits']}")
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"peak device memory: {stats['peak_bytes_in_use']:,} bytes")
    print(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
