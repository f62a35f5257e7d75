"""One run of one cell: build the routed pool, warm it, serve an open loop,
measure a window, and check what the window served against the reference.

The program under test is driven only through its entry points:
``build_model`` per tier, ``make_engine``, ``ContinuousPoolEngine`` over a
``ThresholdPolicy(HybridRouter)``. The harness times the calls the pool
makes (``policy.decide``, each tier's ``step``) by wrapping them on the
instances, and records their host spans with ``TraceAnnotation`` so a
device trace can attribute idle gaps to what the host was doing.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import reference, traffic, weights
from .spec import tier_kind
from .traffic import Arrival

FINISHED = ("length", "eos")


# ------------------------------------------------------------------ building
def arch_config(model: dict):
    """The program's ``ArchConfig`` for a tier's ``model`` block: the
    served precision, the Pallas kernels and the paged cache always."""
    from repro.models.config import ArchConfig
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    unknown = set(model) - fields
    if unknown:
        raise KeyError(f"model keys the program does not know: "
                       f"{sorted(unknown)}")
    return ArchConfig(**{**model, "dtype": "bfloat16", "use_pallas": True,
                         "cache_layout": "paged"})


def router_config(router: dict):
    from repro.models.encoder import RouterConfig
    keys = {f.name for f in dataclasses.fields(RouterConfig)}
    return RouterConfig(**{k: v for k, v in router.items() if k in keys},
                        dtype="bfloat16")


@dataclasses.dataclass
class Tier:
    name: str
    spec: dict                # the config file's tier entry
    engine: object            # the program's ContinuousEngine


class Timed:
    """Wraps a bound method: host clock and a profiler span around every
    call, the spans kept in ``spans``."""

    def __init__(self, fn, name: str, spans: list):
        self.fn, self.name, self.spans = fn, name, spans

    def __call__(self, *a, **kw):
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(self.name):
            out = self.fn(*a, **kw)
        self.spans.append((t0, time.monotonic()))
        return out


class Recorder:
    """Wraps an engine's jitted decode or prefill step and, while
    ``on``, records the work each dispatch carries: for decode, the
    context of every active slot; for prefill, each row's (start, n_new).
    Reads only the inputs the engine handed the step."""

    def __init__(self, fn, kind: str, out: list):
        self.fn, self.kind, self.out, self.on = fn, kind, out, False

    def __call__(self, *a):
        if self.on:
            t = time.monotonic()
            if self.kind == "decode":
                lens, active = np.asarray(a[6]), np.asarray(a[7])
                self.out.append((t, (lens[active] + 1).tolist()))
            else:
                start, n_new = np.asarray(a[6]), np.asarray(a[7])
                self.out.append((t, [(int(s), int(n)) for s, n
                                     in zip(start, n_new) if n > 0]))
        return self.fn(*a)


class Run:
    """A cell's routed pool, built from the seed."""

    def __init__(self, config: dict, seed: int):
        from repro.core import HybridRouter, ThresholdPolicy
        from repro.models import build_model
        from repro.models.encoder import init_router_encoder
        from repro.serving import (ContinuousEngine, ContinuousPoolEngine,
                                   make_engine)
        self.config, self.seed = config, seed
        self.spans: Dict[str, list] = {"router": []}
        self.work: Dict[str, list] = {}
        self.tiers: List[Tier] = []
        self.params = []
        for t, spec in enumerate(config["tiers"]):
            cfg = arch_config(spec["model"])
            bundle = build_model(cfg)
            shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
            params = make_tier_params(spec, shapes, seed, t)
            eng = make_engine(bundle, params, **spec["engine"])
            if not isinstance(eng, ContinuousEngine):
                raise RuntimeError(f"{spec['name']}: make_engine did not "
                                   "build the continuous engine")
            self.params.append(params)
            self.tiers.append(Tier(spec["name"], spec, eng))
        self.rcfg = router_config(config["router"])
        rshapes = jax.eval_shape(lambda k: init_router_encoder(k, self.rcfg),
                                 jax.random.PRNGKey(0))
        # the router's weights are the configuration's, not the run's: a
        # run's seed must not move which requests reach the large tier
        self.rparams = weights.make_params(
            rshapes, weights.seed_key(int(config["router"]["weights_seed"]),
                                      len(config["tiers"])))
        self.router = HybridRouter(self.rparams, self.rcfg, 0.0)
        self.policy = ThresholdPolicy(self.router)
        self.policy.decide = Timed(self.policy.decide, "router",
                                   self.spans["router"])
        self.pool = ContinuousPoolEngine(
            self.policy, [(t.name, t.engine) for t in self.tiers])
        self.recorders: List[Recorder] = []
        for i, t in enumerate(self.tiers):
            self.spans[f"tier{i}"] = []
            t.engine.step = Timed(t.engine.step, f"tier{i}.step",
                                  self.spans[f"tier{i}"])
            for kind, attr in (("decode", "_decode"),
                               ("prefill", "_prefill_chunk_fn")):
                out = self.work.setdefault(f"tier{i}.{kind}", [])
                rec = Recorder(getattr(t.engine, attr), kind, out)
                setattr(t.engine, attr, rec)
                self.recorders.append(rec)

    # ------------------------------------------------------------- warm-up
    def warm(self, prompt_lens, router_lens) -> int:
        """Compile (or load from the persistent cache) every step shape
        the traffic's prompts can reach, and no others. Returns the number
        of shapes warmed."""
        n = 0
        for t in self.tiers:
            n += warm_engine(t.engine, prompt_lens)
        for L in sorted(router_lens):
            toks = np.zeros((1, L), np.int32)
            np.asarray(self.router.scores(jnp.asarray(toks),
                                          jnp.asarray(np.ones_like(toks))))
            n += 1
        return n

    def calibrate(self, prompts: List[np.ndarray], tier0_share: float
                  ) -> float:
        """Gate the router at the score quantile that sends ``tier0_share``
        of the calibration draw to tier 0 (score >= threshold)."""
        scores = []
        for p in prompts:
            row, mask = padded(p)
            scores.append(float(np.asarray(self.router.scores(
                jnp.asarray(row), jnp.asarray(mask)))[0]))
        thr = float(np.quantile(scores, 1.0 - tier0_share))
        self.router.threshold = thr
        return thr

    def reseed(self, seed: int) -> None:
        """New weights from ``seed`` for every tier, made after the old
        ones are dropped, so two sets never share the chip."""
        self.seed = seed
        for t, tier in enumerate(self.tiers):
            shapes = jax.eval_shape(tier.engine.bundle.init,
                                    jax.random.PRNGKey(0))
            tier.engine.params = self.params[t] = None
            p = make_tier_params(tier.spec, shapes, seed, t)
            tier.engine.params = self.params[t] = p

    def reset_pools(self) -> None:
        """Fresh device pools after ``free_state`` (every slot idle)."""
        for t in self.tiers:
            e = t.engine
            e.cache.pool = e.bundle.init_paged_cache(e.cache.num_pages,
                                                     e.cache.page_size)
            if e.rstate is not None:
                e.rstate.state = e.bundle.init_recurrent_state(e.n_slots + 1)

    def record_work(self, on: bool) -> None:
        for r in self.recorders:
            r.on = on

    def stats(self) -> List[dict]:
        return [dataclasses.asdict(t.engine.stats) for t in self.tiers]

    def free_state(self) -> None:
        """Drop the pools' device state, so the reference runs in what the
        serving state held."""
        for t in self.tiers:
            t.engine.cache.pool = None
            if t.engine.rstate is not None:
                t.engine.rstate.state = None


def make_tier_params(spec: dict, shapes, seed: int, t: int):
    """Tier ``t``'s weights from the seed, at its token-embedding scale
    (its file's ``weights.embed_std``) and with its kind's stack axes."""
    return weights.make_params(
        shapes, weights.seed_key(seed, t),
        float(spec.get("weights", {}).get("embed_std", 0.02)),
        getattr(tier_kind(spec["reference"]), "stack_axes",
                weights.stack_axes))


def padded(tokens: np.ndarray):
    """A prompt as the router sees it: one row, zero-padded to the power
    of two at or above its length (at least 32), with its mask."""
    L = traffic.bucket(len(tokens), 32)
    row = np.zeros((1, L), np.int32)
    row[0, :len(tokens)] = tokens
    mask = np.zeros((1, L), np.int32)
    mask[0, :len(tokens)] = 1
    return row, mask


def prefill_shapes(eng, prompt_len: int) -> List[tuple]:
    """(width, page bound) of every chunk the engine dispatches for a
    prompt of ``prompt_len`` tokens, admitted alone (``prefill_pack=1``
    makes every dispatch one row)."""
    out, pos = [], 0
    for w in eng.chunk_widths(prompt_len):
        n = min(w, prompt_len - pos)
        out.append((w, eng._pages_bound(pos + n)))
        pos += n
    return out


def warm_engine(eng, prompt_lens) -> int:
    """Trace every decode page bound and prefill (width, bound) the
    engine can reach on these prompt lengths, through its own jitted
    steps, with no slot active (writes land on the scratch page and row)."""
    if eng.prefill_pack != 1:
        raise ValueError("warm-up enumerates one-row prefill dispatches; "
                         "the cell must set prefill_pack=1")
    cache = eng.cache
    lo = min(prompt_lens)
    cap = cache.max_pages_per_slot * cache.page_size
    bounds = sorted({eng._pages_bound(c) for c in range(lo + 1, cap + 1)})
    chunks = sorted({s for L in set(prompt_lens)
                     for s in prefill_shapes(eng, int(L))})
    B, mp = eng.n_slots, cache.max_pages_per_slot
    rec = lambda: eng.rstate.state if eng.rstate is not None else None
    for bound in bounds:
        pt, sl = cache.device_tables()
        nxt, _, kp, vp, r = eng._decode(
            eng.params, cache.pool["k_pages"], cache.pool["v_pages"], rec(),
            jnp.zeros((B, 1), jnp.int32), pt, sl,
            jnp.asarray(np.zeros((B,), bool)), eng._next_key(),
            jnp.asarray(eng._temps.copy()), bound, 0)
        cache.pool = {"k_pages": kp, "v_pages": vp}
        if eng.rstate is not None:
            eng.rstate.state = r
        np.asarray(nxt)
    x = None
    for w, bound in chunks:
        z = np.zeros((1,), np.int32)
        x, kp, vp, r = eng._prefill_chunk_fn(
            eng.params, cache.pool["k_pages"], cache.pool["v_pages"], rec(),
            jnp.asarray(np.zeros((1, w), np.int32)),
            jnp.asarray(np.zeros((1, mp), np.int32)), jnp.asarray(z),
            jnp.asarray(z), jnp.asarray(z), bound, 0)
        cache.pool = {"k_pages": kp, "v_pages": vp}
        if eng.rstate is not None:
            eng.rstate.state = r
    if x is not None:
        np.asarray(eng._lm_head(eng.params, x)[:, 0])
    return len(bounds) + len(chunks) + 1


# ------------------------------------------------------------- open loop
@dataclasses.dataclass
class Served:
    """One arrival as the harness saw it."""
    arrival: Arrival
    due: float                 # monotonic time the request was due
    submit: float = 0.0        # monotonic time it was submitted
    req: object = None         # the program's Request
    tier: int = -1
    score: float = float("nan")


class OpenLoop:
    """Submits arrivals when due, steps the pool in between, and sleeps
    when there is nothing to do."""

    def __init__(self, run: Run, arrivals: List[Arrival], origin: float):
        self.run, self.origin = run, origin
        self.items = [Served(a, origin + a.due_s) for a in arrivals]
        self.next = 0
        self.waits: list = []

    def submit_due(self, now: float) -> None:
        pool = self.run.pool
        while self.next < len(self.items) and self.items[self.next].due <= now:
            s = self.items[self.next]
            with jax.profiler.TraceAnnotation("submit"):
                row, mask = padded(s.arrival.tokens)
                s.submit = time.monotonic()
                reqs, tiers, scores = pool.submit(
                    row, mask, max_new_tokens=np.asarray([s.arrival.out_cap]),
                    temperature=0.0)
            s.req, s.tier, s.score = reqs[0], int(tiers[0]), float(scores[0])
            self.next += 1

    def serve_until(self, stop: float, done=None) -> None:
        """Run the loop until ``stop`` (monotonic), or until ``done()``."""
        pool = self.run.pool
        while True:
            now = time.monotonic()
            if now >= stop or (done is not None and done()):
                return
            self.submit_due(now)
            if pool.has_work:
                pool.step()
                continue
            due = self.items[self.next].due if self.next < len(self.items) \
                else stop
            wake = min(due, stop)
            if wake > now:
                t0 = time.monotonic()
                with jax.profiler.TraceAnnotation("wait"):
                    time.sleep(wake - now)
                self.waits.append((t0, time.monotonic()))


def request_record(s: Served) -> dict:
    r = s.req
    return {"due": s.due, "submit": s.submit, "segment": s.arrival.segment,
            "prompt_len": int(len(s.arrival.tokens)),
            "cap": int(s.arrival.out_cap), "tier": s.tier, "score": s.score,
            "start": float(r.start_t) if r is not None and r.start_t else None,
            "token_t": list(r.token_t) if r is not None else [],
            "reason": r.finish_reason if r is not None else None,
            "done": bool(r.done) if r is not None else False}


# ----------------------------------------------------------------- checking
def _sample(items: List[Served], k: int, rng: np.random.Generator
            ) -> List[Served]:
    """``k`` of ``items`` drawn from the seed, always with the one that
    served the most tokens and the one with the longest sequence."""
    if not items:
        return []
    most = max(items, key=lambda s: s.req.n_generated)
    longest = max(items, key=lambda s: len(s.arrival.tokens)
                  + s.req.n_generated)
    rest = [s for s in items if s is not most and s is not longest]
    pick = [rest[i] for i in rng.choice(len(rest), min(k, len(rest)),
                                        replace=False)] if rest else []
    return [most] + ([longest] if longest is not most else []) + pick


def bf16_step(x: np.ndarray) -> np.ndarray:
    """The spacing of bfloat16 values (8 significant bits) at ``x``."""
    e = np.floor(np.log2(np.maximum(np.abs(x), np.finfo(np.float32).tiny)))
    return np.exp2(e - 7)


def served_gaps(ref, items: List[Served], ctrl=None) -> List[tuple]:
    """For each request, how far each served token's logit lies below the
    reference's best (or, with ``ctrl``, how far the token the control
    puts first lies below it), and the bfloat16 spacing at the best
    logit. The program takes its greedy pick from bfloat16 logits, so any
    two logits that round to one value tie and the lower id wins: a
    served token may lie up to one such step below the best, where no
    step of the computation before the logits went wrong."""
    if not items:
        return []
    seqs, rows, outs = [], [], []
    for s in items:
        prompt = s.arrival.tokens
        out = np.asarray(s.req.out, np.int32)
        seqs.append(np.concatenate([prompt, out[:-1]]).astype(np.int32))
        rows.append(np.arange(len(prompt) - 1, len(prompt) + len(out) - 1,
                              dtype=np.int32))
        outs.append(out)
    if ctrl is not None:
        outs = [best for _, best, _ in ctrl.gaps(seqs, rows, outs)]
    return [(gap, bf16_step(top)) for gap, _, top
            in ref.gaps(seqs, rows, outs)]


# requests compared per tier (besides the longest two) and prompts scored
# by the reference router: some hundreds of served tokens per tier, and a
# check shorter than the window
PER_TIER, ROUTER_SAMPLE = 8, 8


def check(run: Run, loop: OpenLoop, seed: int, limits: dict,
          control: Optional[str] = None,
          parts: Optional[set] = None) -> dict:
    """The numbers compared, each with its limit. ``control`` names a
    lower precision computed in the program's place (the control);
    ``parts``, where given, the references to run ("tier0", "tier1", ...,
    "router"), the others left out."""
    rng = traffic.run_rng(seed, 3)
    ends = [s for s in loop.items[:loop.next]
            if s.req is not None and s.req.done
            and s.req.finish_reason in FINISHED]
    out: Dict[str, dict] = {}
    for t, tier in enumerate(run.tiers):
        Ref = tier_kind(tier.spec["reference"]).Reference
        ref = Ref(run.params[t], tier.spec["model"])
        ctrl = Ref(run.params[t], tier.spec["model"], quant=control) \
            if control else None
        picked = _sample([s for s in ends if s.tier == t], PER_TIER, rng)
        if parts is not None and f"tier{t}" not in parts:
            continue
        t0 = time.monotonic()
        gaps = served_gaps(ref, picked, ctrl)
        print(f"reference tier {t}: {len(picked)} requests in "
              f"{time.monotonic() - t0:.1f} s", flush=True)
        flat, step = (np.concatenate(a) for a in zip(*gaps)) if gaps else (
            np.full((1,), np.nan),) * 2
        excess = np.maximum(flat - step, 0.0)
        for stat, value in (("max_gap", flat.max()), ("mean_gap", flat.mean()),
                            ("max_excess", excess.max())):
            out[f"tier{t}.{stat}"] = {
                "value": float(value), "limit": limits.get(f"tier{t}.{stat}"),
                "tokens": int(len(flat)), "requests": len(picked)}
    if parts is not None and "router" not in parts:
        return out
    # the router: scores of a sample of the served prompts, and the side
    # of the gate each prompt went to
    rc = run.config["router"]
    ids = rng.choice(loop.next, min(ROUTER_SAMPLE, loop.next), replace=False)
    diffs, mism = [], 0
    lim = limits.get("router.max_score_gap") or 0.0
    t0 = time.monotonic()
    for i in ids:
        s = loop.items[int(i)]
        row, mask = padded(s.arrival.tokens)
        ref_score = float(reference.router_score(
            run.rparams, jnp.asarray(row[0]), jnp.asarray(mask[0]),
            rc["n_heads"], rc["rel_buckets"], rc["rel_max_distance"],
            float(rc["norm_eps"]), control))
        diffs.append(abs(ref_score - s.score))
        want = 0 if ref_score >= run.router.threshold else 1
        if abs(ref_score - run.router.threshold) > lim and want != s.tier:
            mism += 1
    print(f"reference router: {len(ids)} prompts in "
          f"{time.monotonic() - t0:.1f} s", flush=True)
    out["router.max_score_gap"] = {"value": float(max(diffs)),
                                   "limit": limits.get("router.max_score_gap"),
                                   "prompts": len(ids)}
    out["router.wrong_side"] = {"value": float(mism), "limit": 0.0}
    return out


def audit(run: Run, loop: OpenLoop) -> Dict[str, dict]:
    """Exact checks of the pool's bookkeeping over everything it served."""
    leaks = sum(len(t.engine.cache.check_refcounts()) for t in run.tiers)
    wrong_len = 0
    for s in loop.items[:loop.next]:
        r = s.req
        if r is None or not r.done:
            continue
        if r.finish_reason == "length" and r.n_generated != s.arrival.out_cap:
            wrong_len += 1
        if r.finish_reason == "eos" and (not r.out or r.out[-1] != 2):
            wrong_len += 1
    return {"pages.refcount_faults": {"value": float(leaks), "limit": 0.0},
            "requests.wrong_length": {"value": float(wrong_len),
                                      "limit": 0.0}}


def passed(checks: Dict[str, dict]) -> bool:
    for c in checks.values():
        v, lim = c["value"], c["limit"]
        if lim is None or not np.isfinite(v) or v > lim:
            return False
    return True
