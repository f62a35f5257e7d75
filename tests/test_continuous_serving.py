"""Continuous-batching serving: paged cache allocator, slot scheduler,
ContinuousEngine parity with the dense engine, admission control, and the
no-barrier hybrid property."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.data import tokenizer as tok
from repro.models import RouterConfig, build_model, init_router_encoder
from repro.core.routing import HybridRouter
from repro.serving import (ContinuousEngine, ContinuousHybridEngine,
                           ContinuousScheduler, Engine, PagedKVCache,
                           Request, make_engine)
from conftest import tiny_cfg


def _bundle(seed=0, **kw):
    cfg = tiny_cfg("dense", **kw)
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(seed))


# ------------------------------------------------------------------ allocator
def test_paged_cache_alloc_free_reuse():
    _, m, _ = _bundle()
    c = PagedKVCache(m, n_slots=2, num_pages=7, page_size=4,
                     max_pages_per_slot=3)
    pages = c.alloc_slot(0, 9)            # 3 pages
    assert len(pages) == 3 and (pages > 0).all()
    assert c.stats.pages_in_use == 3
    assert not c.can_admit(13)            # 4 pages > free(3) and > cap
    p2 = c.alloc_slot(1, 5)               # 2 pages
    assert c.stats.pages_in_use == 5 and c.stats.high_water_pages == 5
    c.free_slot(0)
    assert c.stats.pages_in_use == 2
    assert (c.page_table[0] == 0).all() and c.seq_lens[0] == 0
    p3 = c.alloc_slot(0, 4)               # freed pages recycled
    assert set(map(int, p3)) <= set(map(int, pages))
    assert c.stats.high_water_pages == 5  # high water unchanged
    assert 0 < c.fragmentation < 1       # tail waste of partial pages
    del p2


def test_paged_cache_append_and_oom():
    _, m, _ = _bundle()
    c = PagedKVCache(m, n_slots=1, num_pages=3, page_size=4,
                     max_pages_per_slot=4)
    c.alloc_slot(0, 4)                    # exactly one full page
    assert c.ensure_append(0)             # boundary -> new page
    assert c.stats.appends == 1
    c.seq_lens[0] = 8                     # fill page 2
    assert not c.ensure_append(0)         # pool exhausted (2 of 2 in use)
    assert c.stats.oom_denials == 1


# ------------------------------------------------------------------ scheduler
def test_scheduler_admission_order_and_slot_reuse():
    s = ContinuousScheduler(2)
    reqs = [s.submit(Request(tokens=np.array([1]), max_new_tokens=4))
            for _ in range(3)]
    a = s.admit()
    b = s.admit()
    assert (a, b) == (reqs[0], reqs[1]) and not s.has_free_slot
    s.retire(a.slot)
    c = s.admit()
    assert c is reqs[2] and c.slot == 0   # freed slot reused
    assert a.done and a.finish_t >= a.submit_t
    assert s.has_work
    s.retire(b.slot)
    s.retire(c.slot)
    assert not s.has_work


# -------------------------------------------------------------------- engine
def test_continuous_matches_dense_greedy():
    """Greedy decode through the paged path, with queueing through fewer
    slots than requests, must reproduce the dense engine exactly."""
    cfg, m, p = _bundle()
    q = np.random.default_rng(0).integers(4, cfg.vocab_size, (5, 12)).astype(np.int32)
    dense = Engine(m, p, max_new_tokens=8)
    r1, l1 = dense.serve(q)
    ce = ContinuousEngine(m, p, max_new_tokens=8, n_slots=2, page_size=8,
                          max_seq=32)
    r2, l2 = ce.serve(q)
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(l1, l2)
    assert ce.stats.admitted == 5 and ce.stats.retired == 5
    assert ce.cache.stats.pages_in_use == 0          # everything freed
    assert ce.cache.stats.high_water_pages <= ce.cache.stats.num_pages


def test_continuous_per_request_length_caps():
    """Each request stops at its own cap — the dense path can't do this."""
    cfg, m, p = _bundle()
    ce = ContinuousEngine(m, p, max_new_tokens=16, n_slots=4, page_size=8,
                          max_seq=64)
    rng = np.random.default_rng(1)
    caps = [1, 3, 9, 16]
    reqs = [ce.submit(rng.integers(4, cfg.vocab_size, (10,)), max_new_tokens=c)
            for c in caps]
    ce.run()
    for req, cap in zip(reqs, caps):
        assert req.done
        assert req.n_generated <= cap
        if tok.EOS not in req.out:
            assert req.n_generated == cap


def test_continuous_admission_stall_then_progress():
    """A pool too small for two prompts queues the second request and admits
    it once the first retires — admission control, not failure."""
    cfg, m, p = _bundle()
    ce = ContinuousEngine(m, p, max_new_tokens=4, n_slots=2, page_size=8,
                          max_seq=32, num_pages=1 + 3)  # 3 usable pages
    rng = np.random.default_rng(2)
    r1 = ce.submit(rng.integers(4, cfg.vocab_size, (12,)))  # needs 2 pages
    r2 = ce.submit(rng.integers(4, cfg.vocab_size, (12,)))  # won't fit with r1
    ce.step()
    assert r1.slot is not None and r2.slot is None
    assert ce.stats.admission_stalls >= 1
    ce.run()
    assert r1.done and r2.done and r2.n_generated > 0


def test_continuous_rejects_oversized_prompt_and_unsupported_family():
    cfg, m, p = _bundle()
    ce = ContinuousEngine(m, p, n_slots=1, page_size=8, max_seq=16)
    # prompts that can never be served are load-shed, not raised: the
    # request comes back already done with finish reason "rejected" and
    # surfaces through the next step() for accounting
    r = ce.submit(np.arange(16, dtype=np.int32) + 4)  # 16 + 1 > 16 cap
    assert r.done and r.finish_reason == "rejected" and r.n_generated == 0
    assert [q is r for q in ce.step()] == [True]
    with pytest.raises(ValueError):
        ce.submit(np.array([], np.int32))             # empty prompt: caller bug
    # a prompt needing more pages than the whole pool can never admit
    ce2 = ContinuousEngine(m, p, n_slots=2, page_size=8, max_seq=32,
                           num_pages=2)               # 1 usable page
    r2 = ce2.submit(np.full((12,), 5, np.int32))      # needs 2 pages
    assert r2.done and r2.finish_reason == "rejected"
    assert ce2.run() == [r2]
    # ssm stacks serve continuously since the recurrent-state pool, but
    # their state streams in through chunked prefill — one-shot admission
    # has no page-shaped state to scatter
    scfg = tiny_cfg("ssm")
    sm = build_model(scfg)
    assert sm.decode_step_paged is not None
    with pytest.raises(ValueError):
        ContinuousEngine(sm, sm.init(jax.random.PRNGKey(0)),
                         prefill_chunk=0)
    # vision-frontend configs need embeds the engine doesn't supply
    assert not tiny_cfg("vlm").supports_paged_kv
    assert tiny_cfg("vlm").paged_unsupported_reason
    assert build_model(tiny_cfg("vlm")).decode_step_paged is None
    with pytest.raises(ValueError):
        ce.submit(np.array([5, 6], np.int32), max_new_tokens=0)


def test_make_engine_cache_layout_dispatch():
    """The cache-layout flag selects the engine; continuous-only kwargs are
    dropped for dense, and unsupported families fall back to dense."""
    cfg, m, p = _bundle()
    assert isinstance(make_engine(m, p, max_new_tokens=4, n_slots=2,
                                  max_seq=32), Engine)
    mp_ = build_model(tiny_cfg("dense", cache_layout="paged"))
    assert isinstance(make_engine(mp_, p, max_new_tokens=4, n_slots=2,
                                  max_seq=32), ContinuousEngine)
    # ssm serves continuously since the recurrent-state pool landed
    ms = build_model(tiny_cfg("ssm", cache_layout="paged"))
    eng = make_engine(ms, ms.init(jax.random.PRNGKey(0)), max_new_tokens=4,
                      n_slots=2, max_seq=32)
    assert isinstance(eng, ContinuousEngine) and eng.rstate is not None
    # encoder-decoder still falls back to the dense engine
    ma = build_model(tiny_cfg("audio", cache_layout="paged"))
    eng = make_engine(ma, ma.init(jax.random.PRNGKey(0)), max_new_tokens=4,
                      n_slots=2, max_seq=32)
    assert isinstance(eng, Engine) and not isinstance(eng, ContinuousEngine)


def test_serve_paths_agree_on_padding(monkeypatch):
    """Padding-parity regression: ContinuousPoolEngine.serve used to fill
    its response matrix with np.zeros while Engine.serve and
    ContinuousEngine.serve pad with tok.PAD, so pool results disagreed with
    every other serve path whenever PAD != 0. Remap PAD to a nonzero id and
    require all three paths to agree elementwise, with PAD tails."""
    import repro.data.tokenizer as tokenizer
    from repro.core.routing import ThresholdPolicy
    from repro.serving.pool import ContinuousPoolEngine
    monkeypatch.setattr(tokenizer, "PAD", 41)
    cfg, m, p = _bundle()
    rng = np.random.default_rng(7)
    # uniform-length prompts: serve() paths must see identical contexts
    # (pool.submit trims by mask, the engines serve rows verbatim)
    q = rng.integers(4, 40, (5, 9)).astype(np.int32)
    mask = np.ones_like(q, np.float32)
    dense = Engine(m, p, max_new_tokens=6)
    rd, ld = dense.serve(q)
    ce = ContinuousEngine(m, p, max_new_tokens=6, n_slots=2, page_size=8,
                          max_seq=32)
    rc, lc = ce.serve(q)
    c0 = ContinuousEngine(m, p, max_new_tokens=6, n_slots=2, page_size=8,
                          max_seq=32)
    pool = ContinuousPoolEngine(ThresholdPolicy(_router(-1.0)),
                                [("small", c0), ("large", c0)])
    res = pool.serve(q, mask)
    np.testing.assert_array_equal(rd, rc)
    np.testing.assert_array_equal(rc, res.responses)
    np.testing.assert_array_equal(ld, lc)
    np.testing.assert_array_equal(lc, res.lengths)
    for i, l in enumerate(res.lengths):
        assert (res.responses[i, l:] == tokenizer.PAD).all()


# -------------------------------------------------------------------- hybrid
def _router(threshold):
    rc = RouterConfig(vocab_size=tok.VOCAB_SIZE, n_layers=1, d_model=32,
                      n_heads=2, d_ff=64)
    params = init_router_encoder(jax.random.PRNGKey(0), rc)
    return HybridRouter(params, rc, threshold)


def test_hybrid_small_stream_progresses_while_large_in_flight():
    """The acceptance property: with admission-time routing, small-engine
    requests retire while the large engine still has work in flight — no
    full-batch barrier between the partitions."""
    cfg = tiny_cfg("dense", vocab_size=tok.VOCAB_SIZE)
    m = build_model(cfg)
    small = ContinuousEngine(m, m.init(jax.random.PRNGKey(1)),
                             max_new_tokens=2, n_slots=4, page_size=8,
                             max_seq=32)
    large = ContinuousEngine(m, m.init(jax.random.PRNGKey(2)),
                             max_new_tokens=16, n_slots=1, page_size=8,
                             max_seq=32)
    rng = np.random.default_rng(0)
    q = rng.integers(4, tok.VOCAB_SIZE, (8, 8)).astype(np.int32)
    mask = np.ones_like(q, np.float32)
    # median threshold -> both partitions populated
    scores = np.asarray(_router(0.5).scores(jnp.asarray(q), jnp.asarray(mask)))
    hy = ContinuousHybridEngine(_router(float(np.median(scores))),
                                small, large)
    reqs, to_small, _ = hy.submit(q, mask)
    assert to_small.any() and (~to_small).any()
    routed_small = {r.rid: bool(s) for r, s in zip(reqs, to_small)}

    small_done_while_large_busy = False
    steps = 0
    while (small.sched.has_work or large.sched.has_work) and steps < 500:
        retired = hy.step()
        steps += 1
        small_retired = [r for r in retired if routed_small[r.rid]]
        if small_retired and large.sched.has_work:
            small_done_while_large_busy = True
    assert small_done_while_large_busy
    assert all(r.done for r in reqs)
    assert hy.meter.to_small + hy.meter.to_large == len(reqs)


def test_hybrid_continuous_serve_compat():
    """Batch-API wrapper returns the HybridResult contract."""
    cfg = tiny_cfg("dense", vocab_size=tok.VOCAB_SIZE)
    m = build_model(cfg)
    small = ContinuousEngine(m, m.init(jax.random.PRNGKey(1)),
                             max_new_tokens=8, n_slots=2, page_size=8,
                             max_seq=32)
    large = ContinuousEngine(m, m.init(jax.random.PRNGKey(2)),
                             max_new_tokens=8, n_slots=2, page_size=8,
                             max_seq=32)
    rng = np.random.default_rng(3)
    q = rng.integers(4, tok.VOCAB_SIZE, (6, 8)).astype(np.int32)
    mask = np.ones_like(q, np.float32)
    hy = ContinuousHybridEngine(_router(-1.0), small, large)  # all -> small
    res = hy.serve(q, mask)
    assert res.responses.shape == (6, 8)
    assert res.routed_small.all()
    assert (res.lengths >= 1).all() and (res.lengths <= 8).all()
    assert hy.meter.cost_advantage == 1.0


# ----------------------------------------------- paged decode block walk
def _ragged_engine(cfg, seed=0, max_new_tokens=3):
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(seed))
    ce = ContinuousEngine(m, p, max_new_tokens=max_new_tokens, n_slots=4,
                          page_size=16, max_seq=512, prefill_chunk=64)
    rng = np.random.default_rng(seed)
    reqs = [ce.submit(rng.integers(4, cfg.vocab_size, (n,)).astype(np.int32))
            for n in (300, 20, 150, 40, 270)]   # one queues for a slot
    return ce, reqs


@pytest.mark.parametrize("window", [0, 6])
def test_decode_block_counters_count_the_kernel_walk(window):
    """``decode_blocks_walked`` / ``decode_blocks_live`` count the paged
    decode kernel's grid steps and those that copy and compute, over every
    attention layer of every plain decode step of a ragged run: recounted
    here block by block from each dispatch's own inputs, a block live when
    it holds a key the slot's query may attend (none for a masked slot)."""
    from repro.kernels.paged_decode_attention.kernel import block_pages
    kw = dict(n_layers=3, sliding_window=window, local_global_ratio=2) \
        if window else {}
    cfg = tiny_cfg("dense", cache_layout="paged", **kw)
    ce, _ = _ragged_engine(cfg)
    seen = []
    decode = ce._decode

    def spy(*args):
        seen.append(args)
        return decode(*args)
    ce._decode = spy
    ce.run()

    ps, K, D = 16, cfg.n_kv_heads, cfg.resolved_head_dim
    cap = ce.cache.max_pages_per_slot * ps
    itemsize = ce.cache.pool["k_pages"].dtype.itemsize
    walked = live = 0
    for args in seen:
        sl, active, bound, wstart = (np.asarray(args[6]),
                                     np.asarray(args[7]), args[10], args[11])
        lens = np.where(active, np.minimum(sl + 1, cap), 0)
        for i in range(cfg.n_layers):
            w = cfg.layer_window(i)
            start = wstart if w else 0
            ppb = block_pages(ps, K, D, itemsize, bound - start)
            n_blocks = -(-(bound - start) // ppb)
            walked += len(lens) * n_blocks
            for n in lens:
                for j in range(n_blocks):
                    first = start + j * ppb
                    live += any(
                        p * ps < n and (not w or (p + 1) * ps > n - w)
                        for p in range(first, min(first + ppb, bound)))
    assert seen and any(b > 16 for *_, b, _ in seen)   # multi-block walks
    assert (ce.stats.decode_blocks_walked,
            ce.stats.decode_blocks_live) == (walked, live)
    assert 0 < live < walked                            # the skip engaged


def test_pallas_decode_kernel_serves_greedy_exact():
    """The engine on the Pallas paged kernels (interpreted) emits the
    reference path's greedy tokens over ragged contexts that span several
    of the decode kernel's blocks, with masked slots and a queued request."""
    cfg = tiny_cfg("dense", cache_layout="paged")
    ref, want = _ragged_engine(cfg, max_new_tokens=8)
    out, got = _ragged_engine(dataclasses.replace(cfg, use_pallas=True),
                              max_new_tokens=8)
    ref.run()
    out.run()
    assert [r.out for r in got] == [r.out for r in want]
