"""Mid-stream quality escalation: greedy-exact continuation contract.

A stream the EscalationMonitor cancels off tier a resumes on tier b as ONE
chunked prefill of (prompt + emitted prefix); every token it emits after
the hand-off must be byte-identical to tier b decoding greedily from that
same prefix — including while the upper tier preempts concurrently, and
when the re-admission walks onto tier b's shared-prefix radix tree. The
abort is made deterministic with ``abort_threshold=0.0``: the uncertainty
score is non-negative, so every monitored stream escalates at exactly
``min_tokens`` emitted tokens.
"""
import jax
import numpy as np
import pytest

from repro.data import tokenizer as tok
from repro.models import build_model
from repro.models.config import ArchConfig
from repro.serving import ContinuousEngine, ContinuousPoolEngine
from repro.serving.engine import EscalationMonitor


class _StaticPolicy:
    """Route everything to one tier (tier 0 unless said otherwise)."""

    def __init__(self, n_tiers, tier=0):
        self._n, self._t = n_tiers, tier

    @property
    def n_tiers(self):
        return self._n

    def decide(self, tokens, mask):
        n = len(tokens)
        return (np.full((n,), self._t, np.int64), np.zeros((n,)))


def _bundles():
    base = dict(family="dense", vocab_size=tok.VOCAB_SIZE,
                vocab_pad_multiple=16, n_layers=2, d_model=32, n_heads=2,
                n_kv_heads=2, d_ff=64, head_dim=16, attn_chunk=16,
                cache_layout="paged", kv_page_size=8)
    out = []
    for name, seed in (("esc-a", 1), ("esc-b", 2)):
        b = build_model(ArchConfig(name=name, **base))
        out.append((b, b.init(jax.random.PRNGKey(seed))))
    return out


def _pool(bundles, max_new=8, min_tokens=3, a_kw=None, b_kw=None):
    """Two-tier pool with a deterministic always-abort monitor on tier a."""
    (ba, pa), (bb, pb) = bundles
    ea = ContinuousEngine(ba, pa, max_new_tokens=max_new,
                          **{"n_slots": 2, "max_seq": 64, "seed": 0,
                             **(a_kw or {})})
    eb = ContinuousEngine(bb, pb, max_new_tokens=max_new,
                          **{"n_slots": 2, "max_seq": 64, "seed": 0,
                             **(b_kw or {})})
    return ContinuousPoolEngine(
        _StaticPolicy(2), [("a", ea), ("b", eb)],
        escalation=[EscalationMonitor(abort_threshold=0.0,
                                      min_tokens=min_tokens)])


def _prompts(n, l=14, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(4, tok.VOCAB_SIZE, (l,)).astype(np.int32)
            for _ in range(n)]


def _reference_continuation(bundles, prompt, prefix, n_tokens):
    """Tier b decoding greedily, uncontended, from (prompt + prefix)."""
    bb, pb = bundles[1]
    eng = ContinuousEngine(bb, pb, max_new_tokens=max(n_tokens, 1),
                           n_slots=2, max_seq=64, seed=7)
    req = eng.submit(np.concatenate([prompt, np.asarray(prefix, np.int32)]))
    eng.run()
    return req.out


def _assert_greedy_exact(pool, bundles, prompts, reqs):
    assert pool.escalation_log, "no stream escalated"
    for rid, ft, tt, k in pool.escalation_log:
        assert (ft, tt) == (0, 1)
        i = next(i for i, r in enumerate(reqs) if r.rid == rid)
        req = reqs[i]
        got = req.out[k:]
        want = _reference_continuation(bundles, prompts[i], req.out[:k],
                                       len(got))[:len(got)]
        assert got == want, f"rid {rid}: {got} != upper-tier {want}"


def test_escalation_continuation_is_greedy_exact():
    bundles = _bundles()
    pool = _pool(bundles, max_new=8, min_tokens=3)
    prompts = _prompts(4)
    reqs = [pool.submit_to(0, p) for p in prompts]
    done = pool.run()
    assert len(done) == 4 and all(r.finish_reason in ("eos", "length")
                                  for r in done)
    # threshold 0.0 + non-negative score: every stream escalates, once,
    # at exactly min_tokens emitted tokens
    assert len(pool.escalation_log) == 4
    assert all(k == 3 for _, _, _, k in pool.escalation_log)
    assert all(r.escalations == 1 and r.esc_peak_score > 0 for r in reqs)
    _assert_greedy_exact(pool, bundles, prompts, reqs)
    # honest accounting: the CALL lands once, at the final tier — §2.3
    # cost metrics undiluted — while token columns split across the tiers
    # that actually emitted
    m = pool.meter
    assert m.total_calls == 4 and list(m.calls) == [0, 4]
    assert list(m.escalations) == [4, 0]
    assert m.esc_tokens[0] == 12 == m.tokens[0]       # 3 tokens x 4 streams
    assert m.tokens.sum() == sum(r.n_generated for r in reqs)
    assert m.cost_advantage == 0.0                    # all calls ended pricey
    assert pool.engines[0].stats.escalations == 4
    assert pool.engines[1].stats.escalations == 0


def test_escalation_survives_concurrent_preemption():
    """A high-priority burst preempts the escalated continuations on the
    upper tier mid-decode; resume is greedy-exact anyway."""
    bundles = _bundles()
    pool = _pool(bundles, max_new=10, min_tokens=2,
                 b_kw=dict(n_slots=1))   # continuations contend on 1 slot
    prompts = _prompts(3, seed=1)
    reqs = [pool.submit_to(0, p) for p in prompts]
    # step until at least one continuation is decoding on tier b, then
    # land a priority burst that evicts it
    for _ in range(200):
        pool.step()
        if any(r.state == "decoding" for r in pool.engines[1].sched.
               running.values()):
            break
    burst = [pool.submit_to(1, p, priority=5) for p in _prompts(2, seed=2)]
    pool.run()
    assert pool.engines[1].stats.preemptions > 0
    assert all(r.done for r in reqs + burst)
    assert len(pool.escalation_log) == 3
    _assert_greedy_exact(pool, bundles, prompts, reqs)


def test_escalated_readmission_hits_prefix_tree():
    """With ``prefix_cache > 0`` on the upper tier, the escalated
    re-prefill of (prompt + emitted prefix) walks onto the radix tree
    instead of recomputing — and the continuation stays byte-identical."""
    bundles = _bundles()
    # phase 1, no sharing: learn each stream's deterministic outputs
    pool0 = _pool(bundles, max_new=8, min_tokens=3)
    prompts = _prompts(3, l=14, seed=3)
    reqs0 = [pool0.submit_to(0, p) for p in prompts]
    pool0.run()
    assert len(pool0.escalation_log) == 3
    # phase 2: fresh pool, tier b shares prefixes. Pre-warm its tree with
    # exactly the continuation prompts (prompt + the 3-token prefix the
    # lower tier deterministically emits): 14 + 3 = 17 tokens -> two full
    # pages published at retirement
    pool = _pool(bundles, max_new=8, min_tokens=3,
                 b_kw=dict(prefix_cache=16, prefill_chunk=8))
    eb = pool.engines[1]
    assert eb.prefix_reason is None
    for p, r0 in zip(prompts, reqs0):
        warm = eb.submit(np.concatenate([p, np.asarray(r0.out[:3],
                                                       np.int32)]))
        eb.run()
        assert warm.done
    pool._tier_of.clear()   # direct engine submits bypassed the registry
    hits_before = eb.stats.prefix_hits
    reqs = [pool.submit_to(0, p) for p in prompts]
    pool.run()
    assert len(pool.escalation_log) == 3
    assert eb.stats.prefix_hits > hits_before
    assert any(r.prefix_hit_tokens > 0 for r in reqs)
    _assert_greedy_exact(pool, bundles, prompts, reqs)
    # sharing changed the dispatch, never the tokens
    for r, r0 in zip(reqs, reqs0):
        assert r.out == r0.out


def test_observe_only_monitor_never_escalates():
    """``abort_threshold=None`` collects per-stream peaks (the calibration
    feed for core.thresholds.calibrate_abort_threshold) without ever
    cancelling anyone."""
    bundles = _bundles()
    pool = _pool(bundles, max_new=6)
    pool.engines[0].escalation = EscalationMonitor(abort_threshold=None)
    # seed 5: every stream decodes past its first step (a stream retired
    # by EOS on its first decode is never scored and keeps a zero peak)
    prompts = _prompts(3, seed=5)
    reqs = [pool.submit_to(0, p) for p in prompts]
    pool.run()
    assert not pool.escalation_log and pool.meter.escalations.sum() == 0
    peaks = [r.esc_peak_score for r in reqs]
    assert all(0 < p <= 1.0 for p in peaks)
    from repro.core.thresholds import calibrate_abort_threshold
    thr = calibrate_abort_threshold(peaks, 0.0)
    assert thr > max(peaks)
    assert calibrate_abort_threshold(peaks, 1.0) <= min(peaks) + 1e-12


@pytest.mark.flaky_quarantine
def test_escalation_storm_stress():
    """Entropy-seeded escalation storm (quarantined: the seed comes from
    OS entropy, so the stream mix — and thus runtime — varies run to run;
    the deterministic tier-1 gate stays reproducible without it, and CI
    runs it in the non-blocking quarantine step). Whatever the draw, the
    hard invariants must hold: every stream retires with a valid reason,
    the token split sums exactly, and no tier leaks a page."""
    rng = np.random.default_rng()   # intentionally unseeded
    bundles = _bundles()
    pool = _pool(bundles, max_new=6, min_tokens=int(rng.integers(1, 4)))
    prompts = [rng.integers(4, tok.VOCAB_SIZE,
                            (int(l),)).astype(np.int32)
               for l in rng.integers(6, 20, (8,))]
    reqs = [pool.submit_to(0, p) for p in prompts]
    done = pool.run()
    assert len(done) == 8
    assert all(r.finish_reason in ("eos", "length") for r in reqs)
    assert len(pool.escalation_log) == 8    # threshold 0.0 always trips
    m = pool.meter
    assert m.tokens.sum() == sum(r.n_generated for r in reqs)
    assert m.total_calls == 8
    for eng in pool.engines:
        assert eng.cache.stats.pages_in_use == 0
        assert not eng.sched.running and not eng.sched.pending


def test_monitor_validation_and_pool_wiring():
    bundles = _bundles()
    with pytest.raises(ValueError):
        EscalationMonitor(min_tokens=0)
    with pytest.raises(ValueError):
        EscalationMonitor(ema=0.0)
    (ba, pa), _ = _bundles()
    eng = ContinuousEngine(ba, pa, max_new_tokens=4, n_slots=2, max_seq=64)
    with pytest.raises(ValueError):   # K-1 monitors, not K
        ContinuousPoolEngine(
            _StaticPolicy(2), [("a", eng), ("b", eng)],
            escalation=[EscalationMonitor(), EscalationMonitor()])
    with pytest.raises(ValueError):   # aliased engine would watch both
        ContinuousPoolEngine(
            _StaticPolicy(2), [("a", eng), ("b", eng)],
            escalation=[EscalationMonitor()])
