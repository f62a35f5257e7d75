"""Continuous model-pool serving: K tiers, one admission-time policy.

``ContinuousPoolEngine`` orchestrates an ordered pool of named
``ContinuousEngine``s (cheapest -> priciest) under a ``RoutingPolicy``
(core.routing): each submitted query is scored once at admission and
enqueued on the engine of its assigned tier; every engine steps
independently, so a cheap tier's requests admit, decode, and retire while
pricier tiers are still in flight — the paper's edge/cloud split (Fig. 2)
generalized from one small/large pair to K tiers. In a real deployment each
engine is a separate device (or device group) and ``step`` is its event
loop.

With ``spec_gamma > 0`` the pool becomes a coordinated *step plane*: a
``StepPlan`` links each expensive tier to its next-cheaper sibling as a
draft model (cross-tier speculative decoding — the token-level
generalization of the paper's per-query routing: the cheap tier drafts
gamma tokens, the expensive tier verifies the whole chunk in one launch,
greedy-exact at temperature 0). Tiers the capability check refuses
(window/SSM/hybrid stacks, one-shot prefill) keep the plain single-step
path, recorded in ``plan.skipped``; a stalled draft tier degrades its
target to plain decode for the stall's duration rather than wedging it.

With ``escalation`` monitors installed, routing stops being final:
each monitored tier watches its own decode logits (entropy / top-2 margin
per step, EMA-smoothed per stream — serving.engine.EscalationMonitor) and
cancels a stream whose running score crosses its boundary's calibrated
abort threshold. The pool drains the escalated buffer every step and
re-admits the request ONE TIER UP as one chunked prefill of prompt +
emitted prefix — escalation costs a prefill, not a restart, and the
continuation is greedy-exact with the upper tier decoding from that same
prefix. The meter bills escalations honestly: tokens split across the
tiers that actually emitted them, the call counts once at the final tier.

Shared-prefix KV reuse is strictly per-tier: an engine built with
``prefix_cache > 0`` keeps its own copy-on-write prefix tree over its own
page pool (serving.prefix) — pages are meaningless across models, so tiers
never share with each other, and a pool freely mixes sharing tiers with
window/SSM tiers that recompute (each records its ``prefix_reason``).

Cost accounting is a ``TierMeter`` (core.routing): per-tier calls and
generated tokens, with calls- and token-weighted cost advantage against the
all-priciest baseline. Engines built with the same default seed get
decorrelated RNG salts at pool construction so temperature>0 tiers never
draw the same sample stream.

``build_fused_pool_step`` is the TPU-side artifact for the dry-run: ONE XLA
program lowering router + all K tiers' decode steps with a tier-select mask
choosing per-query logits. XLA needs static shapes, so every tier runs over
the full batch and the mask selects — the dry-run uses this to prove the
whole pool stack (router included) shards on the production mesh. Cost
accounting on real hardware comes from the host-side engines, where the
partition is physical, not masked.

The two-tier special case keeps its paper-era API as thin facades in
serving.hybrid (``ContinuousHybridEngine`` / ``build_fused_hybrid_step``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.routing import RoutingPolicy, TierMeter
from repro.data import tokenizer as tok
from repro.models.encoder import RouterConfig, router_encode
from repro.models.model import ModelBundle
from .engine import ContinuousEngine, EscalationMonitor
from .scheduler import Request

Engines = Union[Mapping[str, ContinuousEngine],
                Sequence[Tuple[str, ContinuousEngine]]]


@dataclasses.dataclass(frozen=True)
class StepPlan:
    """The pool's coordinated step plane for cross-tier speculative
    decoding: which expensive tier drafts on which cheap sibling.

    ``gamma`` is the draft-chunk length per speculative round (0 disables
    speculation entirely — the pool steps each engine independently,
    exactly the pre-speculative behavior). ``pairs`` holds (draft_tier,
    target_tier) index pairs; the default plan links every tier t >= 1 to
    its next-cheaper sibling t-1. ``skipped`` records every wanted pair
    the capability check refused, with the reason — window/SSM/hybrid
    tiers cannot roll back a rejected suffix and keep the plain
    single-step path, visibly rather than silently."""
    gamma: int = 0
    pairs: Tuple[Tuple[int, int], ...] = ()
    skipped: Tuple[Tuple[int, str], ...] = ()

    @property
    def draft_of(self) -> Dict[int, int]:
        """target tier index -> its draft tier index."""
        return {t: d for d, t in self.pairs}

    @staticmethod
    def _refusal(draft: ContinuousEngine, target: ContinuousEngine) -> str:
        """Why (draft, target) cannot speculate — "" when they can. The
        same contract ``ContinuousEngine.attach_draft`` enforces by
        raising; the plan pre-filters so refusals degrade to the plain
        step path instead of failing pool construction."""
        if draft is target:
            return "draft and target tiers share one engine"
        tb, db = target.bundle, draft.bundle
        if tb.verify_paged_chunk is None:
            return (f"{tb.cfg.name}: recurrent state or sliding-window "
                    "layers cannot roll back a rejected draft suffix")
        if target.prefill_chunk == 0:
            return (f"{tb.cfg.name}: one-shot prefill — the verify chunk "
                    "rides the chunked-prefill machinery")
        if db.decode_step_paged is None or db.prefill_paged_chunk is None:
            return (f"{db.cfg.name}: a draft must serve paged with "
                    "chunked prefill")
        if db.init_recurrent_state is not None or db.cfg.has_window_layers:
            return (f"{db.cfg.name}: a draft must be pure global "
                    "attention (its cache mirrors the target's pages)")
        return ""

    @classmethod
    def build(cls, engines: Sequence[ContinuousEngine], gamma: int,
              pairs: Optional[Sequence[Tuple[int, int]]] = None
              ) -> "StepPlan":
        """Pair each target tier with its draft (default: tier t drafts on
        tier t-1), keeping only capability-approved pairs."""
        if gamma < 0:
            raise ValueError(f"spec_gamma={gamma}: the draft-chunk length "
                             "cannot be negative (0 disables speculation)")
        if gamma == 0:
            return cls()
        wanted = [(t - 1, t) for t in range(1, len(engines))] \
            if pairs is None else [(int(d), int(t)) for d, t in pairs]
        ok: List[Tuple[int, int]] = []
        skipped: List[Tuple[int, str]] = []
        targets: set = set()
        for d, t in wanted:
            if not (0 <= d < len(engines) and 0 <= t < len(engines)) \
                    or d == t:
                raise ValueError(f"spec pair ({d}, {t}) is not two distinct "
                                 f"tiers of a {len(engines)}-tier pool")
            if t in targets:
                raise ValueError(f"tier {t} named as target twice")
            targets.add(t)
            reason = cls._refusal(engines[d], engines[t])
            if reason:
                skipped.append((t, reason))
            else:
                ok.append((d, t))
        return cls(gamma=gamma, pairs=tuple(ok), skipped=tuple(skipped))


@dataclasses.dataclass
class PoolResult:
    """Batch-API result: responses/lengths row-aligned with the submitted
    queries, ``tier_idx`` the policy's dispatch (0 = cheapest tier)."""
    responses: np.ndarray   # (N, T)
    lengths: np.ndarray     # (N,)
    tier_idx: np.ndarray    # (N,) int
    scores: np.ndarray      # (N,)


class ContinuousPoolEngine:
    """Admission-time policy-routed serving over K independently-stepping
    continuous engines. No tier's stream ever barriers on another."""

    def __init__(self, policy: RoutingPolicy, engines: Engines, *,
                 spec_gamma: int = 0,
                 spec_pairs: Optional[Sequence[Tuple[int, int]]] = None,
                 escalation: Optional[
                     Sequence[Optional[EscalationMonitor]]] = None):
        items = list(engines.items()) if isinstance(engines, Mapping) \
            else list(engines)
        if len(items) != policy.n_tiers:
            raise ValueError(f"policy routes over {policy.n_tiers} tiers but "
                             f"the pool has {len(items)} engines: "
                             f"{[n for n, _ in items]}")
        self.policy = policy
        self.names: Tuple[str, ...] = tuple(n for n, _ in items)
        self.engines: List[ContinuousEngine] = [e for _, e in items]
        # cross-tier speculative decoding: spec_gamma > 0 builds the step
        # plane (StepPlan) and hosts each draft tier's model inside its
        # target engine (attach_draft). Tiers a capability check refuses
        # (window/SSM/hybrid, one-shot prefill) stay on the plain path,
        # recorded in plan.skipped. spec_gamma=0 restores today's
        # independent stepping exactly.
        self.plan = StepPlan.build(self.engines, spec_gamma, spec_pairs)
        for d, t in self.plan.pairs:
            de = self.engines[d]
            self.engines[t].attach_draft(de.bundle, de.params,
                                         self.plan.gamma)
        # engines are typically built with the same default seed; distinct
        # salts keep their temperature>0 sample streams uncorrelated. Only
        # distinct engine objects are bumped (a tier may legitimately alias
        # another's engine in tests/toys).
        seen_salts: set = set()
        for eng in self._distinct_engines():
            if eng._rng_salt in seen_salts:
                eng.set_rng_salt(max(seen_salts) + 1)
            seen_salts.add(eng._rng_salt)
        self.meter = TierMeter(self.names)
        self._tier_of: Dict[int, int] = {}   # rid -> tier idx
        # mid-stream quality escalation: one optional monitor per boundary
        # (K-1 entries, cheapest boundary first — the priciest tier has
        # nothing above it to escalate to). Monitors are per-ENGINE state,
        # so installing one on a tier whose engine aliases another tier's
        # would silently watch both; refuse that.
        if escalation is not None:
            if len(escalation) != self.n_tiers - 1:
                raise ValueError(
                    f"a {self.n_tiers}-tier pool has {self.n_tiers - 1} "
                    f"escalation boundaries, got {len(escalation)} monitors")
            for t, mon in enumerate(escalation):
                if mon is None:
                    continue
                if any(self.engines[t] is e for i, e in enumerate(self.engines)
                       if i != t):
                    raise ValueError(
                        f"tier {self.names[t]!r} shares its engine with "
                        "another tier; an escalation monitor there would "
                        "watch both")
                self.engines[t].escalation = mon
        # rid -> generated tokens already billed to lower tiers (token
        # columns split across tiers; the call never splits), and the
        # audit log of every hand-off: (rid, from_tier, to_tier,
        # n_generated at the abort)
        self._esc_billed: Dict[int, int] = {}
        self.escalation_log: List[Tuple[int, int, int, int]] = []

    @property
    def n_tiers(self) -> int:
        return len(self.engines)

    def engine(self, name: str) -> ContinuousEngine:
        return self.engines[self.names.index(name)]

    @property
    def has_work(self) -> bool:
        # shed buffers count: a request rejected at submit still needs one
        # step() to surface and hit the meter. Escalated buffers count
        # too: a stream awaiting its hand-off holds no scheduler slot
        return any(e.sched.has_work or e._shed_buf or e._escalated_buf
                   for e in self.engines)

    # -------------------------------------------------------------- requests
    def submit(self, query_tokens: np.ndarray, query_mask: np.ndarray,
               max_new_tokens: Optional[np.ndarray] = None,
               trim_padding: bool = True, priority: int = 0,
               deadline_s: Optional[float] = None,
               timeout_s: Optional[float] = None,
               temperature: Optional[Union[float, np.ndarray]] = None
               ) -> Tuple[List[Request], np.ndarray, np.ndarray]:
        """Score and enqueue a batch of queries. Returns (requests,
        tier_idx, scores); requests retire later via step()/run() — except
        load-shed ones (finish reason "rejected"), which come back already
        done and hit the meter at the next step().

        ``max_new_tokens``: optional per-request output caps (N,).
        ``trim_padding``: drop each row's PAD tail (from ``query_mask``)
        before enqueueing — paged prefill only pays for real tokens.
        ``priority`` / ``deadline_s`` / ``timeout_s`` apply to the whole
        batch (see ContinuousEngine.submit); use ``submit_to`` for
        per-request robustness attributes. ``temperature``: per-request
        sampling temperatures — a scalar for the whole batch or an (N,)
        array (None = each engine's default, 0 = greedy) — so greedy and
        sampled streams coexist in one pool."""
        tier_idx, scores = self.policy.decide(query_tokens, query_mask)
        tier_idx = np.asarray(tier_idx, np.int64)
        if tier_idx.size and (tier_idx.min() < 0
                              or tier_idx.max() >= self.n_tiers):
            # fail at the call site: a negative index would silently wrap
            # to the priciest engine and only crash at retire time
            raise ValueError(f"policy returned tier indices outside "
                             f"[0, {self.n_tiers}): {np.unique(tier_idx)}")
        with TraceAnnotation("pool.enqueue"):
            reqs = []
            for i, (row, tier) in enumerate(zip(query_tokens, tier_idx)):
                eng = self.engines[int(tier)]
                if trim_padding:
                    # trim to one past the last true mask position — a mask
                    # with interior holes has sum() < that, and trimming to
                    # sum() would drop real prompt tokens
                    nz = np.flatnonzero(np.asarray(query_mask[i]))
                    row = row[:int(nz[-1]) + 1] if len(nz) else row[:1]
                cap = int(max_new_tokens[i]) if max_new_tokens is not None \
                    else None
                temp = None if temperature is None else float(
                    temperature[i] if np.ndim(temperature) else temperature)
                req = eng.submit(row, max_new_tokens=cap, priority=priority,
                                 deadline_s=deadline_s, timeout_s=timeout_s,
                                 temperature=temp)
                self._tier_of[req.rid] = int(tier)
                reqs.append(req)
        return reqs, tier_idx, scores

    def submit_to(self, tier: Union[int, str], tokens: np.ndarray,
                  max_new_tokens: Optional[int] = None, *,
                  priority: int = 0, deadline_s: Optional[float] = None,
                  timeout_s: Optional[float] = None,
                  temperature: Optional[float] = None) -> Request:
        """Enqueue one request directly on a named (or indexed) tier,
        bypassing the routing policy — the ops/fault-injection entry point
        (targeted bursts, health probes). Accounting is identical to
        policy-routed traffic."""
        t = self.names.index(tier) if isinstance(tier, str) else int(tier)
        if not 0 <= t < self.n_tiers:
            raise ValueError(f"tier {tier!r} not in pool {self.names}")
        req = self.engines[t].submit(tokens, max_new_tokens=max_new_tokens,
                                     priority=priority, deadline_s=deadline_s,
                                     timeout_s=timeout_s,
                                     temperature=temperature)
        self._tier_of[req.rid] = t
        return req

    def _account(self, retired: List[Request]):
        for req in retired:
            # pop: the registry must not grow for the life of the process
            tier = self._tier_of.pop(req.rid)
            # tokens this stream already billed to lower tiers at each
            # escalation hand-off (record_escalation); the final tier only
            # bills what it emitted itself, so the token split sums to
            # n_generated exactly
            billed_below = self._esc_billed.pop(req.rid, 0)
            if req.finish_reason == "rejected":
                # shed, not served: no call/token record, or the §2.3 cost
                # metrics would dilute with traffic no tier ran
                self.meter.record_shed(tier)
                continue
            self.meter.record(np.array([tier]),
                              req.n_generated - billed_below)
            self.meter.record_robustness(
                tier, preemptions=req.preemptions,
                reprefill_tokens=req.reprefill_tokens,
                deadline_miss=req.finish_reason == "deadline")
            if req.drafted_tokens and tier in self.plan.draft_of:
                # drafted tokens bill to the CHEAP tier (its model ran
                # them), accepted/rejected to the target — side-channel
                # columns, so §2.3 cost metrics stay undiluted
                self.meter.record_spec(
                    self.plan.draft_of[tier], tier,
                    drafted=req.drafted_tokens,
                    accepted=req.accepted_tokens,
                    rejected=req.rejected_tokens)

    def _handoff(self, req: Request) -> None:
        """Deliver one escalated stream to the next tier up: bill the
        abandoning tier the tokens it actually emitted for this stream
        (record_escalation — tokens split across tiers, the CALL never
        splits: it lands once, at whatever tier finally retires the
        request), move the rid's registry entry up a tier, log the
        hand-off, and re-queue on the upper engine. A continuation the
        upper tier could never fit sheds there ("rejected") and surfaces
        through its shed buffer next step."""
        t = self._tier_of[req.rid]
        if t + 1 >= self.n_tiers:
            # the engine-side monitor check cannot know its pool position;
            # the pool must never install a monitor on the priciest tier
            raise RuntimeError(
                f"stream {req.rid} escalated off the priciest tier "
                f"{self.names[t]!r} — monitor misconfiguration")
        billed = self._esc_billed.get(req.rid, 0)
        self.meter.record_escalation(t, req.n_generated - billed)
        self._esc_billed[req.rid] = req.n_generated
        self._tier_of[req.rid] = t + 1
        self.escalation_log.append((req.rid, t, t + 1, req.n_generated))
        self.engines[t + 1].resubmit(req)

    def _distinct_engines(self) -> List[ContinuousEngine]:
        """Engines deduped by identity, cheapest-tier-first: a tier may
        alias another's engine, which must still step (and reseed) once."""
        out: List[ContinuousEngine] = []
        for eng in self.engines:
            if not any(eng is e for e in out):
                out.append(eng)
        return out

    def step(self, stalled: Sequence[str] = ()) -> List[Request]:
        """Advance every engine by one full step each (admission, packed
        prefill chunks, a speculative round over plan-paired tiers then
        one decode token per remaining DECODING slot, retirement — see
        ContinuousEngine.step), cheapest tier first, with no cross-engine
        join. ``stalled`` names tiers to skip this step — the
        fault-injection hook for a wedged device: its queue holds, the
        other tiers keep streaming. A target tier whose DRAFT tier is
        stalled still steps but with speculation off (``spec=False``):
        it degrades to plain decode rather than deadlocking on a wedged
        draft device, and the draft cache catches up when the stall
        lifts. Returns the requests retired this step."""
        skip = [self.engine(n) for n in stalled]
        stalled_idx = {self.names.index(n) for n in stalled}
        no_spec = [self.engines[t] for d, t in self.plan.pairs
                   if d in stalled_idx]
        retired: List[Request] = []
        for eng in self._distinct_engines():
            # submit-time sheds drain even from a stalled tier: rejection
            # happens host-side at the front door, not on the device
            retired.extend(eng.drain_shed())
            if eng.sched.has_work and not any(eng is s for s in skip):
                retired.extend(eng.step(
                    spec=not any(eng is s for s in no_spec)))
            # escalated hand-offs drain even from a stalled tier: the
            # hand-off is host-side bookkeeping, and parking a cancelled
            # stream in a wedged tier's buffer would stall it twice
            with TraceAnnotation("pool.account"):
                for req in eng.drain_escalated():
                    self._handoff(req)
        with TraceAnnotation("pool.account"):
            self._account(retired)
        return retired

    def run(self) -> List[Request]:
        done: List[Request] = []
        while self.has_work:
            done.extend(self.step())
        return done

    # ----------------------------------------------------------- compat API
    def serve(self, query_tokens: np.ndarray, query_mask: np.ndarray,
              seed: int = 0) -> PoolResult:
        """Batch-API wrapper: submit every row, drain, join the results."""
        for eng in self._distinct_engines():
            eng.reseed(seed)
        reqs, tier_idx, scores = self.submit(query_tokens, query_mask)
        self.run()
        T = max(e.max_new_tokens for e in self.engines)
        N = len(reqs)
        # PAD, not zeros: every other serve path (Engine.serve,
        # ContinuousEngine.serve) pads response tails with tok.PAD, and the
        # two only coincide when PAD happens to be 0
        responses = np.full((N, T), tok.PAD, np.int32)
        lengths = np.zeros((N,), np.int32)
        for i, req in enumerate(reqs):
            lengths[i] = req.n_generated
            responses[i, :req.n_generated] = req.out[:T]
        return PoolResult(responses, lengths, tier_idx, scores)


def build_fused_pool_step(router_cfg: RouterConfig,
                          bundles: Sequence[ModelBundle],
                          thresholds: Sequence[float]):
    """One-token K-tier decode step as a single lowerable program.

    ``bundles`` are ordered cheapest -> priciest; ``thresholds`` are the K-1
    non-increasing cascade gates (core.thresholds.cascade_thresholds).

    fn(router_params, params_tuple, router_tokens, router_mask, caches_tuple,
       token) -> (logits, caches_tuple, tier_idx)

    Every tier decodes the full batch (XLA needs static shapes); the
    tier-select mask picks each query's logits. Vocabs may differ in
    padding, so logits align on the smallest padded width.
    """
    thresholds = tuple(float(t) for t in thresholds)
    if len(thresholds) != len(bundles) - 1:
        raise ValueError(f"{len(bundles)} tiers need {len(bundles) - 1} "
                         f"cascade thresholds, got {len(thresholds)}")
    if any(a < b for a, b in zip(thresholds, thresholds[1:])):
        raise ValueError(f"cascade thresholds must be non-increasing: "
                         f"{thresholds}")

    def step(router_params, params, router_tokens, router_mask, caches,
             token):
        score = jax.nn.sigmoid(router_encode(router_params, router_tokens,
                                             router_mask, router_cfg))
        tier = jnp.zeros(score.shape, jnp.int32)                   # (B,)
        for t in thresholds:
            tier += (score < t).astype(jnp.int32)
        outs = [b.decode_step(p, c, token)
                for b, p, c in zip(bundles, params, caches)]
        V = min(l.shape[-1] for l, _ in outs)
        stacked = jnp.stack([l[:, :V] for l, _ in outs])           # (K, B, V)
        logits = jnp.take_along_axis(stacked, tier[None, :, None],
                                     axis=0)[0]
        return logits, tuple(c for _, c in outs), tier

    return step
