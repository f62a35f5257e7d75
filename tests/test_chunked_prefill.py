"""Chunked bucketed paged prefill: kernel vs ref sweeps, exact greedy parity
with the one-shot prefill path across chunk widths and ragged prompt
lengths, one-compile-per-bucketed-width, the no-decode-stall property, and
the satellite fixes (cost-meter lengths, RNG decorrelation, latency /
finish_reason, trim_padding)."""
import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.routing import CostMeter, HybridRouter
from repro.data import tokenizer as tok
from repro.kernels.paged_prefill_attention.kernel import \
    paged_prefill_attention_gqa
from repro.kernels.paged_prefill_attention.ref import \
    paged_prefill_attention_ref
from repro.models import RouterConfig, build_model, init_router_encoder
from repro.serving import (ContinuousEngine, ContinuousHybridEngine, Engine,
                           HybridEngine, PagedKVCache, Request)
from repro.serving.scheduler import DECODING, PREFILLING
from conftest import tiny_cfg

NEG_INF = -1e30
LAYER = 1   # the pool layer the kernels read; the references get kp[LAYER]


def _bundle(seed=0, **kw):
    cfg = tiny_cfg("dense", **kw)
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(seed))


def _make_paged(rng, B, K, D, ps, MP, totals):
    """Random stacked page pools (LAYER + 1, P, K, ps, D) + a page table
    giving each request distinct pages covering ``totals[b]`` tokens (page 0
    reserved as scratch)."""
    n_pages = 1 + sum(-(-int(t) // ps) for t in totals)
    # LAYER + 1 layers: the kernels must read LAYER, not another layer
    shape = (LAYER + 1, n_pages, K, ps, D)
    kp = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    vp = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    pt = np.zeros((B, MP), np.int32)
    nxt = 1
    for b in range(B):
        for i in range(-(-int(totals[b]) // ps)):
            pt[b, i] = nxt
            nxt += 1
    return kp, vp, jnp.asarray(pt)


# ------------------------------------------------------------------- kernel
@pytest.mark.parametrize("G,ps,D,C", [(1, 8, 32, 4), (2, 16, 64, 8),
                                      (4, 8, 128, 16), (8, 32, 32, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_prefill_kernel_matches_ref(G, ps, D, C, dtype):
    rng = np.random.default_rng(G * ps + D + C)
    B, K, MP = 3, 2, 6
    total = rng.integers(1, MP * ps + 1, (B,))
    n_new = np.minimum(total, rng.integers(1, C + 1, (B,)))
    start = jnp.asarray(total - n_new, jnp.int32)
    total = jnp.asarray(total, jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, K, C, G, D)), dtype) * (D ** -0.5)
    kp, vp, pt = _make_paged(rng, B, K, D, ps, MP, np.asarray(total))
    kp, vp = kp.astype(dtype), vp.astype(dtype)
    out = paged_prefill_attention_gqa(q, kp, vp, LAYER, pt, start, total,
                                      interpret=True)
    ref = paged_prefill_attention_ref(q, kp[LAYER], vp[LAYER], pt, start,
                                      total)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


def test_paged_prefill_ref_matches_dense_causal_oracle():
    """Gathering the pages into a dense key space and running plain causal
    attention for the chunk's query positions must agree with the paged
    reference — masking/layout equivalence."""
    rng = np.random.default_rng(5)
    B, K, G, D, ps, MP, C = 2, 2, 2, 32, 8, 4, 4
    total = np.array([9, 30])
    n_new = np.array([3, 4])
    start = total - n_new
    q = jnp.asarray(rng.standard_normal((B, K, C, G, D)), jnp.float32) \
        * (D ** -0.5)
    kp, vp, pt = _make_paged(rng, B, K, D, ps, MP, total)
    kp, vp = kp[LAYER], vp[LAYER]
    out = paged_prefill_attention_ref(q, kp, vp, pt, jnp.asarray(start),
                                      jnp.asarray(total))
    S = MP * ps
    kd = jnp.moveaxis(kp[pt], 2, 1).reshape(B, K, S, D)
    vd = jnp.moveaxis(vp[pt], 2, 1).reshape(B, K, S, D)
    s = jnp.einsum("bkcgd,bksd->bkcgs", q, kd).astype(jnp.float32)
    qpos = jnp.asarray(start)[:, None] + jnp.arange(C)     # (B, C)
    valid = jnp.arange(S)[None, None, :] <= qpos[:, :, None]
    valid &= jnp.arange(S)[None, None, :] < jnp.asarray(total)[:, None, None]
    s = jnp.where(valid[:, None, :, None, :], s, NEG_INF)
    oracle = jnp.einsum("bkcgs,bksd->bkcgd",
                        jax.nn.softmax(s, axis=-1).astype(vd.dtype), vd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=3e-5, atol=3e-5)


def test_paged_prefill_live_bound_matches_full_walk():
    """A pages_bound covering every row's total must reproduce the full
    static page walk exactly, kernel and ref."""
    rng = np.random.default_rng(13)
    B, K, G, D, ps, MP, C = 3, 2, 2, 32, 8, 6, 4
    bound = 3
    total = rng.integers(1, bound * ps + 1, (B,))
    n_new = np.minimum(total, rng.integers(1, C + 1, (B,)))
    start = jnp.asarray(total - n_new, jnp.int32)
    total = jnp.asarray(total, jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, K, C, G, D)), jnp.float32) \
        * (D ** -0.5)
    kp, vp, pt = _make_paged(rng, B, K, D, ps, MP, np.asarray(total))
    full = paged_prefill_attention_gqa(q, kp, vp, LAYER, pt, start, total,
                                       interpret=True)
    bk = paged_prefill_attention_gqa(q, kp, vp, LAYER, pt, start, total,
                                     pages_bound=bound, interpret=True)
    br = paged_prefill_attention_ref(q, kp[LAYER], vp[LAYER], pt, start,
                                     total, pages_bound=bound)
    np.testing.assert_allclose(np.asarray(bk), np.asarray(full),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(br), np.asarray(full),
                               rtol=3e-5, atol=3e-5)


def test_paged_prefill_ops_layout():
    """Model entry: q (B, C, H, D) regrouped to GQA, H = K * G."""
    from repro.kernels.paged_prefill_attention import ops as ppa_ops
    rng = np.random.default_rng(7)
    B, K, G, D, ps, MP, C = 2, 2, 2, 32, 8, 3, 4
    H = K * G
    total = np.array([7, 20])
    n_new = np.array([4, 2])
    start = jnp.asarray(total - n_new)
    total = jnp.asarray(total)
    q = jnp.asarray(rng.standard_normal((B, C, H, D)), jnp.float32) \
        * (D ** -0.5)
    kp, vp, pt = _make_paged(rng, B, K, D, ps, MP, np.asarray(total))
    out = ppa_ops.paged_prefill_attention(q, kp, vp, LAYER, pt, start, total)
    qg = jnp.transpose(q.reshape(B, C, K, G, D), (0, 2, 1, 3, 4))
    ref = paged_prefill_attention_ref(qg, kp[LAYER], vp[LAYER], pt, start,
                                      total)
    ref = jnp.transpose(ref, (0, 2, 1, 3, 4)).reshape(B, C, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


# ------------------------------------------------------------ engine parity
@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_chunked_matches_oneshot_greedy(chunk):
    """Greedy decode after chunked admission must reproduce the one-shot
    prefill path exactly, across chunk widths and ragged prompt lengths."""
    cfg, m, p = _bundle()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(4, cfg.vocab_size, (l,)).astype(np.int32)
               for l in (3, 12, 17, 5, 9, 24, 1)]

    def serve(prefill_chunk):
        ce = ContinuousEngine(m, p, max_new_tokens=8, n_slots=2, page_size=8,
                              max_seq=64, prefill_chunk=prefill_chunk)
        reqs = [ce.submit(t) for t in prompts]
        ce.run()
        return [r.out for r in reqs], ce

    base, _ = serve(0)                      # one-shot reference
    out, ce = serve(chunk)
    assert out == base
    assert ce.stats.prefill_chunks > 0
    assert ce.stats.prefill_tokens == sum(len(t) for t in prompts)
    assert ce.cache.stats.pages_in_use == 0
    with pytest.raises(ValueError):
        ContinuousEngine(m, p, n_slots=2, max_seq=32, prefill_chunk=-chunk)


def test_chunk_compiles_one_per_bucketed_width():
    """Legacy per-slot static-walk admission traces exactly one prefill
    shape per bucketed chunk width — resubmitting any mix of lengths adds
    no compiles."""
    cfg, m, p = _bundle()
    W = 8
    ce = ContinuousEngine(m, p, max_new_tokens=2, n_slots=2, page_size=8,
                          max_seq=64, prefill_chunk=W, prefill_pack=0,
                          walk_bound="static")
    rng = np.random.default_rng(1)
    lens = [3, 8, 11, 16, 20, 2, 7]

    def bucket(n):
        b = 1
        while b < n:
            b *= 2
        return b

    widths = set()
    for l in lens:
        r = l
        while r:
            w = W if r >= W else bucket(r)
            widths.add(w)
            r -= min(r, w)
    for l in lens:
        ce.submit(rng.integers(4, cfg.vocab_size, (l,)).astype(np.int32))
    ce.run()
    assert ce.stats.prefill_compiles == len(widths)
    for l in reversed(lens):                # same lengths: nothing retraces
        ce.submit(rng.integers(4, cfg.vocab_size, (l,)).astype(np.int32))
    ce.run()
    assert ce.stats.prefill_compiles == len(widths)


def test_packed_live_compiles_stay_bounded():
    """Packed + live-bounded admission traces one shape per bucketed
    (batch, width, page-bound) triple — every axis drawn from a power-of-two
    bucket set — and resubmitting the same lengths adds no compiles."""
    cfg, m, p = _bundle()
    W, n_slots = 8, 4
    ce = ContinuousEngine(m, p, max_new_tokens=2, n_slots=n_slots,
                          page_size=8, max_seq=64, prefill_chunk=W)
    rng = np.random.default_rng(1)
    lens = [3, 8, 11, 16, 20, 2, 7]
    for l in lens:
        ce.submit(rng.integers(4, cfg.vocab_size, (l,)).astype(np.int32))
    ce.run()
    compiles = ce.stats.prefill_compiles

    def log2ceil(n):
        b, c = 1, 1
        while b < n:
            b *= 2
            c += 1
        return c

    # each compile key is a (batch-bucket, width-bucket, bound-bucket)
    # triple; the bucket sets bound the worst case
    widths = {w for l in lens for w in ce.chunk_widths(l)}
    max_bounds = log2ceil(ce.cache.max_pages_per_slot)
    assert compiles <= log2ceil(n_slots) * len(widths) * max_bounds
    assert ce.stats.decode_compiles <= max_bounds
    for l in lens:        # same lengths, same order: nothing retraces
        ce.submit(rng.integers(4, cfg.vocab_size, (l,)).astype(np.int32))
    ce.run()
    assert ce.stats.prefill_compiles == compiles


def test_decode_progresses_while_long_prompt_prefills():
    """The tentpole property, at the shipped default budget: a long prompt
    admits chunk-by-chunk (at most one chunk per slot per step) while a
    live decode slot keeps emitting a token every step — admission no
    longer stalls decode for the whole-prompt prefill."""
    cfg, m, p = _bundle()
    ce = ContinuousEngine(m, p, max_new_tokens=40, n_slots=2, page_size=8,
                          max_seq=64, prefill_chunk=4)
    rng = np.random.default_rng(2)
    a = ce.submit(rng.integers(4, cfg.vocab_size, (2,)).astype(np.int32))
    ce.step()
    assert a.state == DECODING and a.n_generated >= 1
    b = ce.submit(rng.integers(4, cfg.vocab_size, (24,)).astype(np.int32))
    prefill_steps = 0
    while b.state != DECODING:
        before = a.n_generated
        ce.step()
        assert a.n_generated == before + 1   # decode never stalled
        if b.state == PREFILLING:
            prefill_steps += 1
        assert b.n_generated == 0 or b.state == DECODING
    assert prefill_steps >= 24 // 4 - 1      # prompt streamed across steps
    assert b.ttft > 0
    ce.run()


def test_ensure_append_respects_prefill_reserve():
    """Decode-time page growth must not take pages promised to a mid-prefill
    slot — otherwise decoders racing an admission could strand it."""
    _, m, _ = _bundle()
    c = PagedKVCache(m, n_slots=2, num_pages=4, page_size=4,
                     max_pages_per_slot=3)
    c.alloc_slot(0, 4)                       # page boundary; 2 pages free
    assert not c.ensure_append(0, reserve=2)  # both free pages are promised
    assert c.stats.oom_denials == 1
    assert c.ensure_append(0, reserve=1)      # one page genuinely free


def test_prefill_reservation_prevents_midprompt_starvation():
    """Admission reserves the remaining prompt pages of mid-prefill slots:
    a second request can't claim pages a half-admitted prompt still needs."""
    cfg, m, p = _bundle()
    # pool of 4 usable pages, page_size 8: a 24-token prompt needs 3
    ce = ContinuousEngine(m, p, max_new_tokens=2, n_slots=2, page_size=8,
                          max_seq=32, num_pages=5, prefill_chunk=8,
                          prefill_budget=8)
    rng = np.random.default_rng(3)
    r1 = ce.submit(rng.integers(4, cfg.vocab_size, (24,)).astype(np.int32))
    r2 = ce.submit(rng.integers(4, cfg.vocab_size, (12,)).astype(np.int32))
    ce.step()   # r1 admitted, first chunk in; r2 must wait (3 reserved + 2)
    assert r1.state == PREFILLING and r2.slot is None
    assert ce.stats.admission_stalls >= 1
    ce.run()
    assert r1.done and r2.done
    assert ce.stats.prefill_stalls == 0     # reservation kept its promise


# ------------------------------------------------- packed / bounded parity
@pytest.mark.parametrize("pack,bound", [(None, "static"), (0, "live"),
                                        (None, "live")])
def test_packed_and_bounded_match_legacy_greedy(pack, bound):
    """The tentpole parity: batched-packed prefill and live-bounded page
    walks must reproduce the legacy per-slot / full-static-walk path
    greedy-exactly, across ragged prompt lengths, mid-stream retirement
    (ragged per-request caps through fewer slots than requests), and
    admission waves through a tight pool."""
    cfg, m, p = _bundle()
    rng = np.random.default_rng(8)
    lens = (3, 24, 1, 17, 9, 12, 5, 20)
    caps = (2, 8, 4, 8, 1, 6, 8, 3)
    prompts = [rng.integers(4, cfg.vocab_size, (l,)).astype(np.int32)
               for l in lens]

    def serve(prefill_pack, walk_bound):
        ce = ContinuousEngine(m, p, max_new_tokens=8, n_slots=3, page_size=8,
                              max_seq=64, num_pages=12, prefill_chunk=8,
                              prefill_pack=prefill_pack,
                              walk_bound=walk_bound)
        reqs = [ce.submit(t, max_new_tokens=c)
                for t, c in zip(prompts, caps)]
        ce.run()
        return [r.out for r in reqs], ce

    base, legacy = serve(0, "static")       # the pre-tentpole path
    out, ce = serve(pack, bound)
    assert out == base
    assert ce.stats.prefill_tokens == legacy.stats.prefill_tokens
    assert ce.cache.stats.pages_in_use == 0


def test_packed_prefill_amortizes_dispatches():
    """Heavy admission: concurrently PREFILLING slots sharing a bucketed
    chunk width advance through ONE kernel launch per step, not one per
    slot — prefill dispatches drop from O(slots) to O(width buckets)."""
    cfg, m, p = _bundle()
    ce = ContinuousEngine(m, p, max_new_tokens=2, n_slots=4, page_size=8,
                          max_seq=64, prefill_chunk=8)
    rng = np.random.default_rng(9)
    reqs = [ce.submit(rng.integers(4, cfg.vocab_size, (32,))
                      .astype(np.int32)) for _ in range(4)]
    ce.run()
    assert all(r.done for r in reqs)
    st = ce.stats
    assert st.prefill_chunks == 16               # 4 slots x 4 chunks each
    assert st.prefill_dispatches == 4            # one per step, all packed
    assert st.prefill_steps == 4
    assert st.prefill_dispatches < st.prefill_chunks


def test_extend_slots_per_row_stall_fallback():
    """Batched page extension: a row the pool can't satisfy returns None
    while later rows still get their pages — one slot's stall never blocks
    the bucket."""
    _, m, _ = _bundle()
    c = PagedKVCache(m, n_slots=3, num_pages=4, page_size=4,
                     max_pages_per_slot=3)
    got = c.extend_slots([0, 1, 2], [8, 8, 4])   # needs 2+2+1, only 3 free
    assert got[0] is not None and len(got[0]) == 2
    assert got[1] is None                        # 1 page left < 2 needed
    assert got[2] is not None and len(got[2]) == 1
    assert c.stats.oom_denials == 1
    assert int(c.seq_lens[1]) == 0               # stalled row untouched


def test_packed_prefill_stall_defers_row_only():
    """Engine-level per-row fallback: when the pool can only extend one of
    two mid-prefill slots, the other defers a step instead of blocking the
    whole pack, and both complete once pages free up."""
    cfg, m, p = _bundle()
    ce = ContinuousEngine(m, p, max_new_tokens=2, n_slots=2, page_size=8,
                          max_seq=64, prefill_chunk=8, prefill_budget=16)
    rng = np.random.default_rng(10)
    r1 = ce.submit(rng.integers(4, cfg.vocab_size, (24,)).astype(np.int32))
    r2 = ce.submit(rng.integers(4, cfg.vocab_size, (24,)).astype(np.int32))
    ce.step()                      # both admitted, first chunks in
    assert r1.prefill_pos == 8 and r2.prefill_pos == 8
    stolen = [ce.cache._free.pop()
              for _ in range(len(ce.cache._free) - 1)]
    ce.step()                      # one page left: r1 extends, r2 stalls
    assert r1.prefill_pos == 16 and r2.prefill_pos == 8
    assert ce.stats.prefill_stalls == 1
    ce.cache._free.extend(stolen)
    ce.run()
    assert r1.done and r2.done


def test_budget_admits_fitting_tail_chunk_same_step():
    """Satellite: the step budget is charged at the bucketed dispatch width
    and over-budget slots are skipped, not break-ed — a non-power-of-two
    ragged tail later in admission order that fits the leftover budget runs
    the same step instead of starving behind a bigger chunk."""
    cfg, m, p = _bundle()
    ce = ContinuousEngine(m, p, max_new_tokens=2, n_slots=3, page_size=8,
                          max_seq=64, prefill_chunk=8, prefill_budget=12)
    rng = np.random.default_rng(11)
    a = ce.submit(rng.integers(4, cfg.vocab_size, (32,)).astype(np.int32))
    b = ce.submit(rng.integers(4, cfg.vocab_size, (24,)).astype(np.int32))
    c = ce.submit(rng.integers(4, cfg.vocab_size, (3,)).astype(np.int32))
    ce.step()
    # budget 12: a's chunk spends 8; b's width-8 chunk exceeds the leftover
    # 4 and is skipped; c's 3-token tail buckets to width 4 and fits — it
    # must run this step, not wait behind b
    assert a.prefill_pos == 8
    assert b.prefill_pos == 0
    assert c.prefill_pos == 3       # tail prefilled same step
    ce.run()
    assert a.done and b.done and c.done


def test_final_chunk_slot_not_double_counted_in_occupancy():
    """A slot whose final chunk lands this step flips to DECODING and
    decodes this same step — it is busy once, not twice, so mean occupancy
    can never exceed the slot count."""
    cfg, m, p = _bundle()
    ce = ContinuousEngine(m, p, max_new_tokens=4, n_slots=1, page_size=8,
                          max_seq=32, prefill_chunk=8)
    rng = np.random.default_rng(14)
    r = ce.submit(rng.integers(4, cfg.vocab_size, (8,)).astype(np.int32))
    ce.step()
    assert r.prefill_pos == 8       # the only chunk landed, then decoded
    assert ce.stats.occupancy_sum <= ce.stats.steps * ce.n_slots
    ce.run()
    assert ce.stats.mean_occupancy <= ce.n_slots


def test_stalled_chunk_refunds_budget_to_skipped_slots():
    """A slot that stalls on pages never dispatched, so its budget charge
    is refunded and a slot previously skipped for budget can still run —
    pool pressure must not make the packed path lose throughput the legacy
    per-slot loop (charge only on success) would have kept."""
    cfg, m, p = _bundle()
    # page_size 4 + chunk 8: a full chunk needs 2 fresh pages, a 3-token
    # tail only 1 — that asymmetry is what lets the tail fit a one-page
    # pool where the full chunks stall
    ce = ContinuousEngine(m, p, max_new_tokens=2, n_slots=3, page_size=4,
                          max_seq=64, prefill_chunk=8, prefill_budget=8)
    rng = np.random.default_rng(15)
    a = ce.submit(rng.integers(4, cfg.vocab_size, (24,)).astype(np.int32))
    b = ce.submit(rng.integers(4, cfg.vocab_size, (24,)).astype(np.int32))
    c = ce.submit(rng.integers(4, cfg.vocab_size, (3,)).astype(np.int32))
    ce.step()    # all admitted; budget 8 lets only a's chunk run
    assert a.prefill_pos == 8 and b.prefill_pos == 0 and c.prefill_pos == 0
    stolen = [ce.cache._free.pop()
              for _ in range(len(ce.cache._free) - 1)]
    ce.step()    # a stalls (needs 2 pages, 1 free) and refunds its budget;
    # b, rescanned, stalls and refunds too; c's 1-page tail then fits
    assert a.prefill_pos == 8 and b.prefill_pos == 0
    assert c.prefill_pos == 3
    assert ce.stats.prefill_stalls == 2
    ce.cache._free.extend(stolen)
    ce.run()
    assert a.done and b.done and c.done


def test_prefill_only_steps_counted_in_occupancy():
    """Satellite: steps that only advanced prefill used to be invisible to
    ``steps``/``occupancy_sum``, so mean occupancy overstated under heavy
    admission. They now count."""
    cfg, m, p = _bundle()
    ce = ContinuousEngine(m, p, max_new_tokens=2, n_slots=2, page_size=8,
                          max_seq=64, prefill_chunk=4, prefill_budget=4)
    rng = np.random.default_rng(12)
    r = ce.submit(rng.integers(4, cfg.vocab_size, (16,)).astype(np.int32))
    ce.run()
    assert r.done
    st = ce.stats
    assert st.prefill_only_steps >= 3    # 16-token prompt, 4-token chunks
    assert st.steps == st.decode_steps + st.prefill_only_steps
    assert st.steps >= st.prefill_steps >= 4
    assert 0 < st.mean_occupancy <= ce.n_slots


# --------------------------------------------------------------- satellites
def test_cost_meter_per_request_lengths():
    m = CostMeter()
    m.record(np.array([True, False, True]), np.array([3, 7, 2]))
    assert m.to_small == 2 and m.to_large == 1
    assert m.small_tokens == 5 and m.large_tokens == 7
    m.record(np.array([True]), 4)           # scalar broadcast still works
    assert m.small_tokens == 9


def _router(threshold):
    rc = RouterConfig(vocab_size=tok.VOCAB_SIZE, n_layers=1, d_model=32,
                      n_heads=2, d_ff=64)
    params = init_router_encoder(jax.random.PRNGKey(0), rc)
    return HybridRouter(params, rc, threshold)


def test_dense_hybrid_meter_charges_realised_lengths():
    """HybridEngine must charge the tokens each request actually generated,
    not the max_new_tokens budget."""
    cfg = tiny_cfg("dense", vocab_size=tok.VOCAB_SIZE)
    m = build_model(cfg)
    # mismatched per-partition budgets: responses must size to the larger
    small = Engine(m, m.init(jax.random.PRNGKey(1)), max_new_tokens=6)
    large = Engine(m, m.init(jax.random.PRNGKey(2)), max_new_tokens=8)
    rng = np.random.default_rng(4)
    q = rng.integers(4, tok.VOCAB_SIZE, (6, 8)).astype(np.int32)
    mask = np.ones_like(q, np.float32)
    scores = np.asarray(_router(0.5).scores(jnp.asarray(q),
                                            jnp.asarray(mask)))
    hy = HybridEngine(_router(float(np.median(scores))), small, large)
    res = hy.serve(q, mask)
    assert res.responses.shape == (6, 8)
    assert hy.meter.small_tokens == int(res.lengths[res.routed_small].sum())
    assert hy.meter.large_tokens == int(res.lengths[~res.routed_small].sum())


def test_request_latency_and_finish_reason():
    req = Request(tokens=np.array([5], np.int32), max_new_tokens=4)
    assert math.isnan(req.latency) and math.isnan(req.ttft)
    cfg, m, p = _bundle()
    ce = ContinuousEngine(m, p, max_new_tokens=4, n_slots=1, page_size=8,
                          max_seq=16)
    rng = np.random.default_rng(5)
    r = ce.submit(rng.integers(4, cfg.vocab_size, (6,)).astype(np.int32))
    ce.run()
    assert r.finish_reason in ("eos", "length")
    assert r.latency >= 0 and r.ttft >= 0 and r.ttft <= r.latency
    # context cap: a 15-token prompt in a 16-token context has room for one
    # decode write — the first token (sampled off the prefill logits) plus
    # one decoded token, then the formerly-silent truncation, now visible
    r2 = ce.submit(rng.integers(4, cfg.vocab_size, (15,)).astype(np.int32),
                   max_new_tokens=4)
    ce.run()
    if tok.EOS not in r2.out:
        assert r2.finish_reason == "context_cap"
        assert r2.n_generated == 2


def test_trim_padding_keeps_interior_mask_holes():
    cfg = tiny_cfg("dense", vocab_size=tok.VOCAB_SIZE)
    m = build_model(cfg)
    small = ContinuousEngine(m, m.init(jax.random.PRNGKey(1)),
                             max_new_tokens=2, n_slots=2, page_size=8,
                             max_seq=32)
    hy = ContinuousHybridEngine(_router(-1.0), small, small)  # all -> small
    q = np.array([[7, 8, 0, 9, 0, 0]], np.int32)
    mask = np.array([[1, 1, 0, 1, 0, 0]], np.float32)   # interior hole
    reqs, _, _ = hy.submit(q, mask)
    assert len(reqs[0].tokens) == 4          # one past last true, not sum()=3
    hy.run()


def test_hybrid_engines_draw_uncorrelated_samples():
    """Two continuous engines built with identical seeds get distinct salts
    inside a hybrid, and repeated serve calls advance the stream."""
    cfg = tiny_cfg("dense", vocab_size=tok.VOCAB_SIZE)
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(1))

    def eng():
        return ContinuousEngine(m, p, max_new_tokens=12, temperature=1.0,
                                n_slots=2, page_size=8, max_seq=32, seed=0)

    e1, e2 = eng(), eng()
    ContinuousHybridEngine(_router(0.5), e1, e2)
    assert e1._rng_salt != e2._rng_salt
    rng = np.random.default_rng(6)
    q = rng.integers(4, tok.VOCAB_SIZE, (4, 6)).astype(np.int32)
    r1, _ = e1.serve(q)
    r2, _ = e2.serve(q)
    assert not np.array_equal(r1, r2)        # salted partitions differ
    r1b, _ = e1.serve(q)
    assert not np.array_equal(r1, r1b)       # serve-call counter advances

    # dense hybrid: the two partitions and successive calls get distinct
    # derived seeds
    small = Engine(m, p, max_new_tokens=12, temperature=1.0)
    hy = HybridEngine(_router(-1.0), small, small)   # all -> "small"
    mask = np.ones_like(q, np.float32)
    a = hy.serve(q, mask, seed=0)
    b = hy.serve(q, mask, seed=0)
    assert not np.array_equal(a.responses, b.responses)
